"""Work functions against hand counts at the cells' shapes."""
import pytest

from bench.peaks import peaks, roofline_s
from bench.work import apply, packet

# (rows, contraction): flops, bytes, counted by hand.
#   packet flops = rows (rows + 1) c + 2 rows c
#   packet bytes = 4 (rows c + c + rows^2 + rows)
PACKETS = {
    # real-sim primal, sb = 256 rows of n = 72,309:
    # 65,792 x 72,309 = 4,757,353,728; + 2 x 18,511,104
    (256, 72309): (4_794_375_936, 74_596_820),
    # news20 dual, 256 columns of d = 62,061
    (256, 62061): (4_114_892_544, 64_061_876),
    # real-sim on 4 chips, 256 rows of one 18,078-column shard
    (256, 18078): (1_198_643_712, 18_847_352),
}
#   apply flops = 2 rows c; bytes = 4 (rows c + rows + c)
APPLIES = {
    (16, 72309): (2_313_888, 4_917_076),     # one block, primal local
    (16, 62061): (1_985_952, 4_220_212),     # one block, dual local
    (256, 18078): (9_255_936, 18_585_208),   # deferred, primal sharded
}


@pytest.mark.parametrize("shape", sorted(PACKETS))
def test_packet_work(shape):
    assert packet.work(*shape, 4) == PACKETS[shape]


@pytest.mark.parametrize("shape", sorted(APPLIES))
def test_apply_work(shape):
    assert apply.work(*shape, 4) == APPLIES[shape]


def test_primal_packet_is_bytes_bound_on_v5e():
    p = peaks("TPU v5 lite")
    flops, nbytes = PACKETS[(256, 72309)]
    # 74.6 MB at 819 GB/s is 91 us; 4.8 GFLOP at 197 TFLOP/s is 24 us.
    assert roofline_s(flops, nbytes, p) == pytest.approx(nbytes / 819e9)
    assert roofline_s(flops, nbytes, p) == pytest.approx(91.08e-6, rel=1e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v4")
    with pytest.raises(KeyError):
        peaks("cpu")

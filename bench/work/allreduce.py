"""Work of one all-reduce of ``words`` words over ``shards`` chips: the
least bytes each chip must send, ``2 (P - 1) / P`` of the payload (a
reduce-scatter and an all-gather of ``1 / P`` chunks each, the bandwidth
optimum of any all-reduce algorithm).  Whatever schedule the compiler
picks, this stays the count, so a schedule that sends more, or waits,
shows as a lower roofline share."""


def work(words: int, shards: int, itemsize: int) -> float:
    """Bytes one chip sends."""
    return 2.0 * (shards - 1) / shards * words * itemsize


def least_s(words: int, shards: int, itemsize: int, peak: dict) -> float:
    """The least time for those bytes over the chip's interconnect
    (``ici_bits_per_s`` in ``peaks.json``)."""
    return work(words, shards, itemsize) * 8.0 / peak["ici_bits_per_s"]

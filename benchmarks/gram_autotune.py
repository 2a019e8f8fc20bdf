"""Gram-packet tile autotuning sweep: measure (bm, bk) candidates per
(sb, n, dtype, layout) operating point and emit the table
``kernels/gram/tuning.py`` consumes (``tuning.load_table`` / the
``REPRO_GRAM_TUNING`` env var).

Both operand layouts are swept through ``gram_packet_sampled``: the
row-sampled packet of ``RowMajorOperand`` (the row-slab gather kernel, which
the primal and the build-once dual operand run) and the column-sampled
packet of the sharded dual's shard-local ``_ColGatherOperand`` (the
lane-slab gather kernel).  Only tiles the chip's
compiler accepts (``tuning.tile_problems``) are candidates.  Three-field
table keys load unchanged, defaulting to row-major.

On TPU (``--impl pallas``) this times the real kernels and the table entries
are meaningful; on the CPU container the ref backend ignores tile sizes, so
the sweep degenerates to recording the heuristic pick per shape bucket --
the table schema and plumbing are exercised end-to-end either way, and a TPU
run of the same command ships real numbers without code changes.

    PYTHONPATH=src python -m benchmarks.gram_autotune [--out PATH] [--impl I]
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from repro.kernels.gram import RowMajorOperand, gram_packet_sampled, tuning
from repro.kernels.gram.operands import _ColGatherOperand

from ._util import row, timed

# Solver operating points, per layout: (sb, contraction).  Rows: sb = s*b
# against n points (or n/P for the sharded local packet).  Cols: the dual's
# sb' = s*b' against the d-length feature contraction.
SHAPES = [(32, 1024), (64, 4096), (128, 4096), (128, 32768)]
COLS_SHAPES = [(32, 512), (64, 4096)]
SMOKE_SHAPES = [(16, 512)]
SMOKE_COLS_SHAPES = [(16, 256)]
DTYPES = [jnp.float32]


def _candidates(m: int, n: int, dtype, layout: str) -> list[tuple[int, int]]:
    bms = (tuning.BM_CANDIDATES if layout == "rows"
           else tuning.BM_CANDIDATES_COLS)
    bks = (tuning.BK_CANDIDATES if layout == "rows"
           else tuning.BK_CANDIDATES_COLS)
    cands = {tuning.clamp_tiles(bm, bk, m, n, dtype, layout)
             for bm in bms for bk in bks
             if not tuning.tile_problems(bm, bk, dtype, layout, m, n)}
    return sorted(cands) or [tuning.pick_tiles(m, n, dtype, layout)]


def _timed_case(m: int, n: int, dtype, layout: str, impl: str, bm: int,
                bk: int) -> float:
    # The contraction has length n; samples come from a pool of 4m (>= 256)
    # rows (row-major) or columns (column-major), built once outside the
    # timed call.
    pool = max(4 * m, 256)
    if layout == "rows":
        op = RowMajorOperand(jax.random.normal(jax.random.key(0), (pool, n),
                                               dtype))
    else:
        op = _ColGatherOperand(jax.random.normal(jax.random.key(0),
                                                 (n, pool), dtype))
    u = jax.random.normal(jax.random.key(1), (n,), dtype)
    flat = jax.random.randint(jax.random.key(2), (m,), 0, pool, jnp.int32)
    fn = jax.jit(lambda op, flat, u: gram_packet_sampled(
        op, flat, u, scale=1.0 / n, impl=impl, bm=bm, bk=bk))
    return timed(fn, op, flat, u)


def sweep(shapes_by_layout: dict, dtypes, impl: str) -> tuple[list[str], dict]:
    """Returns (CSV rows, table mapping bucket-key -> best (bm, bk))."""
    rows, table = [], {}
    tile_sweep = impl in ("pallas",)  # ref ignores tiles; interpret is Python
    for dtype in dtypes:
        dname = jnp.dtype(dtype).name
        for layout, shapes in shapes_by_layout.items():
            for m, n in shapes:
                cands = (_candidates(m, n, dtype, layout) if tile_sweep
                         else [tuning.pick_tiles(m, n, dtype, layout=layout)])
                best, best_us = None, float("inf")
                for bm, bk in cands:
                    us = _timed_case(m, n, dtype, layout, impl, bm, bk)
                    if us < best_us:
                        best, best_us = (bm, bk), us
                key = (tuning._bucket(tuning._round_up(m, tuning.ROW_GRANULE)),
                       tuning._bucket(tuning.padded_contraction(n, dtype,
                                                                layout)),
                       dname, layout)
                table[f"{key[0]},{key[1]},{key[2]},{key[3]}"] = list(best)
                rows.append(row(f"autotune/gram_{layout}_{m}x{n}_{dname}",
                                best_us,
                                f"bm={best[0]} bk={best[1]} impl={impl} "
                                f"layout={layout} swept={len(cands)}"))
    return rows, table


def write_table(table: dict, impl: str, out: str) -> None:
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump({"backend": impl, "jax_backend": jax.default_backend(),
                   "table": table}, f, indent=2, sort_keys=True)


DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "out",
                           "gram_tuning.json")


def run(impl: str | None = None, smoke: bool = False,
        out: str | None = DEFAULT_OUT) -> list[str]:
    """``out`` defaults to benchmarks/out/gram_tuning.json so harness runs
    (``make bench`` / ``make bench-smoke``) persist the swept table -- on TPU
    that file is exactly what ``REPRO_GRAM_TUNING`` consumes.  Pass
    ``out=None`` to sweep without writing."""
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "ref")
    shapes = ({"rows": SMOKE_SHAPES, "cols": SMOKE_COLS_SHAPES} if smoke
              else {"rows": SHAPES, "cols": COLS_SHAPES})
    rows, table = sweep(shapes, DTYPES, impl)
    if out:
        write_table(table, impl, out)
        tuning.register_table(table)   # make this process benefit immediately
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--impl", default=None, help="ref | pallas | pallas_interpret")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for line in run(impl=args.impl, smoke=args.smoke, out=args.out):
        print(line)


if __name__ == "__main__":
    main()

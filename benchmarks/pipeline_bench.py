"""Pipelined wire-schedule benchmark (DESIGN.md section 9): the alpha-beta
hop model of the ring decomposition vs the monolithic psum, at the dryrun
production mesh, plus a measured equivalence cell on the local CPU world.

The headline row is ``solver/overlap_ratio``: the fraction of the ring
reduction's wire time the pipelined scan hides behind the next step's Gram
contraction and the tenants' sweeps, modeled on the TPU-v5e ICI constants at
the batched serving point (T=4096 tenants, s=8, b=8 on the 16x16 production
mesh).  Single-tenant cells are latency-bound -- 60 hops against sub-
microsecond compute -- so their ratio is honestly near zero and recorded as
such; the acceptance bar (> 0.5) lives where the schedule actually pays.

Rows are modeled (no wire exists off-TPU); the numerical-equivalence claim
behind them (ring == psum to f64 ~1e-12) is machine-checked in
tests/dist_checks.py and the repro.analysis sweep.
"""
from __future__ import annotations

from repro.core.cost_model import (TPU_V5E_ICI, pipeline_schedule,
                                   psum_wire_time, ring_wire_time)

from ._util import row

# The dry-run production mesh (launch/solver_dryrun.make_production_mesh):
# 256 chips as (16, 16).
PROD_AXES = (16, 16)
# The batched serving point of serve_bench / DESIGN.md section 8.
PROD = dict(d=4096, n=1 << 22, b=8, s=8)
TENANTS = 4096


def run(smoke: bool = False) -> list[str]:
    rows = []
    m = TPU_V5E_ICI

    # headline: overlap at the batched serving point (the acceptance row)
    sch = pipeline_schedule(m, axis_sizes=PROD_AXES, tenants=TENANTS, **PROD)
    rows.append(row(
        "solver/overlap_ratio", sch["t_wire_ring"] * 1e6,
        f"overlap_ratio={sch['overlap_ratio']:.3f} tenants={TENANTS} "
        f"mesh={'x'.join(map(str, PROD_AXES))} s={PROD['s']} b={PROD['b']} "
        f"hops={sch['hops']:.0f} modeled=alpha-beta(tpu-v5e-ici)"))
    rows.append(row(
        "solver/exposed_wire_us", sch["t_exposed_ring"] * 1e6,
        f"ring_exposed={sch['t_exposed_ring']*1e6:.1f}us "
        f"psum_exposed={sch['t_exposed_psum']*1e6:.1f}us "
        f"step_speedup={sch['step_speedup']:.2f}x tenants={TENANTS}"))

    # the honest single-tenant cell: latency-bound, near-zero overlap
    sch1 = pipeline_schedule(m, axis_sizes=PROD_AXES, tenants=1, **PROD)
    rows.append(row(
        "solver/overlap_ratio_single", sch1["t_wire_ring"] * 1e6,
        f"overlap_ratio={sch1['overlap_ratio']:.3f} tenants=1 "
        f"(latency-bound: {sch1['hops']:.0f} hops vs "
        f"{sch1['t_compute']*1e6:.2f}us compute)"))

    # raw wire comparison at the packet payload, no overlap credit
    sb = PROD["s"] * PROD["b"]
    payload = sb * sb + TENANTS * sb
    P = PROD_AXES[0] * PROD_AXES[1]
    rows.append(row(
        "solver/wire_ring_vs_psum", ring_wire_time(m, payload, PROD_AXES) * 1e6,
        f"ring_us={ring_wire_time(m, payload, PROD_AXES)*1e6:.1f} "
        f"psum_us={psum_wire_time(m, payload, P)*1e6:.1f} "
        f"payload_words={payload}"))
    return rows

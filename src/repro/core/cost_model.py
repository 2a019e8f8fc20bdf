"""The paper's alpha-beta-gamma running-time model (Eq. 1, Tables 1-2) plus the
modeled strong/weak scaling experiments of Figures 8-9, extended with TPU-pod
machine models (DESIGN.md section 2).

T = gamma * F + alpha * L + beta * W

with per-algorithm critical-path costs.  Leading constants follow the proofs of
Theorems 1/2/6/7 (Gram + residual + subproblem + vector updates); Big-O
constants the paper drops are kept as explicit small integers so the modeled
curves are reproducible, and dropping them shifts all curves proportionally
(paper footnote 3).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import numpy as np


@dataclasses.dataclass(frozen=True)
class MachineModel:
    name: str
    gamma: float   # seconds per flop
    alpha: float   # seconds per message
    beta: float    # seconds per word moved


# NERSC Cori constants from the paper (section 5.2, ref [1]); Spark raises the
# effective latency to 1e-3 s per reduction (scheduling/centralization, ref [20]).
CORI_MPI = MachineModel("cori-mpi", gamma=8e-13, alpha=1e-6, beta=1.3e-10)
CORI_SPARK = MachineModel("cori-spark", gamma=8e-13, alpha=1e-3, beta=1.3e-10)

# TPU v5e adaptation (hardware constants from the assignment): 197 TFLOP/s bf16
# per chip, ~50 GB/s/link ICI, ~1 us collective launch.  Words are 4 bytes to
# stay commensurate with the paper's model.  The DCN (inter-pod) model carries
# the Spark-like latency penalty: O(100 us) software-driven reductions.
TPU_V5E_ICI = MachineModel("tpu-v5e-ici", gamma=1 / 197e12, alpha=1e-6, beta=4 / 50e9)
TPU_V5E_DCN = MachineModel("tpu-v5e-dcn", gamma=1 / 197e12, alpha=1e-4, beta=4 / 2.5e9)

MACHINES = {m.name: m for m in (CORI_MPI, CORI_SPARK, TPU_V5E_ICI, TPU_V5E_DCN)}


@dataclasses.dataclass(frozen=True)
class Costs:
    flops: float      # F
    latency: float    # L (number of messages)
    bandwidth: float  # W (words moved)
    memory: float     # M (words per processor)

    def time(self, m: MachineModel) -> float:
        return m.gamma * self.flops + m.alpha * self.latency + m.beta * self.bandwidth


def _logp(P: float) -> float:
    return max(math.log2(max(P, 2)), 1.0)


def bcd_costs(d: int, n: int, P: int, b: int, H: int, s: int = 1) -> Costs:
    """Theorem 1 (s=1) / Theorem 6 (s>1), 1D-block-column layout.

    Per outer iteration (every s inner iterations): one (sb x sb) Gram
    all-reduce fused with the residual, s local b x b Cholesky solves, local
    vector updates.
    """
    outer = H / s
    sb = s * b
    gram_flops = sb * sb * n / P + sb * n / P          # Y Y^T + residual panel
    solve_flops = s * (b ** 3 / 3 + 2 * b * b) + sb * sb * s  # chol + subst + corrections
    update_flops = sb + sb * n / P                     # w and alpha updates
    F = outer * (gram_flops + solve_flops + update_flops)
    L = outer * 2 * _logp(P)                           # one fused all-reduce (tree up+down)
    W = outer * (sb * sb + sb) * _logp(P)
    M = d * n / P + sb * sb + 2 * sb + d + 2 * n / P
    return Costs(F, L, W, M)


def bdcd_costs(d: int, n: int, P: int, b: int, H: int, s: int = 1) -> Costs:
    """Theorem 2 (s=1) / Theorem 7 (s>1), 1D-block-row layout; b is b'."""
    outer = H / s
    sb = s * b
    gram_flops = sb * sb * d / P + sb * d / P
    solve_flops = s * (b ** 3 / 3 + 2 * b * b) + sb * sb * s
    update_flops = sb + sb * d / P
    F = outer * (gram_flops + solve_flops + update_flops)
    L = outer * 2 * _logp(P)
    W = outer * (sb * sb + sb) * _logp(P)
    M = d * n / P + sb * sb + 2 * sb + n + 2 * d / P
    return Costs(F, L, W, M)


def snapshot_cadence(machine: MachineModel, *, d: int, n: int, P: int, b: int,
                     s: int, mtbf_outer: float, formulation: str = "primal",
                     ) -> dict:
    """Young's rule for the supervisor's snapshot interval, in OUTER steps.

    The solver carry snapshot is the logical iterate pair (w in R^d, alpha in
    R^n) -- ``d + n`` words gathered and written once, modeled as one message
    (``t_snap = alpha + beta (d + n)``).  One outer step costs the
    formulation's Theorem 6/7 critical path at H = s (``t_step``).  With
    failures arriving every ``mtbf_outer`` outer steps on average, the
    classical first-order optimum balances snapshot overhead ``t_snap / k``
    against expected replay ``k t_step / (2 mtbf)``:

        k* = sqrt(2 * mtbf_outer * t_snap / t_step)

    Returns ``{"cadence", "t_snap", "t_step", "overhead"}`` -- cadence is
    k* clamped to >= 1, overhead the per-step fraction
    ``t_snap / (k* t_step) + k* t_step / (2 mtbf t_step)`` the supervisor
    pays for resilience (DESIGN.md section 7 carries the worked example).
    """
    if mtbf_outer <= 0:
        raise ValueError(f"mtbf_outer={mtbf_outer} must be > 0")
    t_snap = machine.alpha + machine.beta * (d + n)
    cost_fn = bdcd_costs if formulation == "dual" else bcd_costs
    t_step = cost_fn(d, n, P, b, s, s).time(machine)
    k = max(1, round(math.sqrt(2 * mtbf_outer * t_snap / t_step)))
    overhead = t_snap / (k * t_step) + k / (2 * mtbf_outer)
    return {"cadence": k, "t_snap": t_snap, "t_step": t_step,
            "overhead": overhead}


def cg_costs(d: int, n: int, P: int, k: int) -> Costs:
    """Krylov row of Table 2: 1D layout, small-dimension vectors replicated."""
    F = k * (4 * d * n / P + 5 * min(d, n))
    L = k * 2 * _logp(P)
    W = k * min(d, n) * _logp(P)
    M = d * n / P + 4 * min(d, n)
    return Costs(F, L, W, M)


def tsqr_costs(d: int, n: int, P: int) -> Costs:
    """TSQR row of Table 2: single reduction over local R factors."""
    c, r = min(d, n), max(d, n)
    F = 2 * c * c * r / P + (2 * c ** 3 / 3) * _logp(P)
    L = _logp(P)
    W = c * c / 2 * _logp(P)
    M = d * n / P + c * c
    return Costs(F, L, W, M)


ALGORITHMS: dict[str, Callable[..., Costs]] = {
    "bcd": bcd_costs, "bdcd": bdcd_costs,
}


# --------------------------------------------------------------------------
# Batched multi-tenant solves (DESIGN.md section 8)
# --------------------------------------------------------------------------
# T tenant solves share ONE operand, ONE block-index stream, and therefore
# ONE sb x sb Gram contraction and ONE psum per outer step; only the (T, sb)
# residual directions, the T subproblem sweeps, and the T vector updates
# scale with the tenant axis.  The sync term (alpha * L) is PER BATCH, not
# per tenant -- that amortization is the whole point of the tenant axis, and
# it is what the solves/s model below exposes: on latency-dominated machines
# throughput grows ~linearly in T until the per-tenant flop/bandwidth terms
# take over.

def batched_costs(d: int, n: int, P: int, b: int, H: int, s: int = 1,
                  tenants: int = 1, formulation: str = "primal") -> Costs:
    """Critical-path costs of ONE T-tenant batched solve of H iterations.

    Shared per outer step: the sb x sb Gram contraction and the (single)
    all-reduce.  Per tenant per outer step: the residual direction, the s
    small Cholesky solves, and the iterate updates -- Theorem 6/7 terms with
    the Gram row paid once.  Wire: sb^2 + T*sb words per outer step (the
    contract the analysis sweep machine-checks).  Memory: the shared operand
    shard plus T iterate/target stripes.
    """
    if tenants < 1:
        raise ValueError(f"tenants={tenants} must be >= 1")
    outer = H / s
    sb = s * b
    c = n if formulation != "dual" else d      # local contraction length
    gram_flops = sb * sb * c / P               # shared: ONE Y Y^T per step
    per_tenant = (sb * c / P                               # residual panel
                  + s * (b ** 3 / 3 + 2 * b * b) + sb * sb * s  # subproblem
                  + sb + sb * c / P)                       # updates
    F = outer * (gram_flops + tenants * per_tenant)
    L = outer * 2 * _logp(P)                   # ONE fused all-reduce, any T
    W = outer * (sb * sb + tenants * sb) * _logp(P)
    other = d if formulation != "dual" else n  # replicated iterate length
    M = d * n / P + sb * sb + tenants * (2 * sb + other + 2 * c / P)
    return Costs(F, L, W, M)


def tenant_bytes_per_iter(d: int, n: int, P: int, b: int, s: int,
                          tenants: int, formulation: str = "primal",
                          itemsize: int = 4) -> float:
    """Wire bytes per ITERATION per TENANT of the batched solve.

    The shared Gram part (sb^2 words per outer step) splits across all T
    tenants, so this drops toward the ``b * logp`` floor of the per-tenant
    residual row as T grows -- the amortization curve serve_bench records
    next to measured solves/s.
    """
    c = batched_costs(d, n, P, b, s, s, tenants, formulation)
    return c.bandwidth * itemsize / (s * tenants)


def batched_solves_per_second(machine: MachineModel, *, d: int, n: int,
                              P: int, b: int, H: int, s: int = 1,
                              tenants: int = 1,
                              formulation: str = "primal") -> float:
    """Modeled solve throughput of the batched engine: T solves of H
    iterations finish in ONE batched critical path, so

        solves/s = T / time(batched_costs(T))

    with the sync term ``alpha * L`` amortized across the tenant axis (L is
    independent of T).  At T=1 this is exactly the single-solve rate."""
    t = batched_costs(d, n, P, b, H, s, tenants, formulation).time(machine)
    return tenants / t


# --------------------------------------------------------------------------
# Wire schedules: monolithic psum vs the pipelined ring (DESIGN.md section 9)
# --------------------------------------------------------------------------
# The Theorem 6/7 rows above charge the packet reduction as a tree all-reduce
# sitting SERIALLY on the critical path: 2 log2(P) messages, payload * log2(P)
# words, nothing overlapped.  The "pipelined" backend decomposes that psum
# into a dimension-wise ring (per mesh axis of size P_i: a reduce-scatter of
# P_i - 1 collective-permute hops followed by an all-gather of P_i - 1 hops)
# and software-pipelines the outer scan so step k+1's Gram contraction -- the
# one packet term with no data dependence on the in-flight reduction -- runs
# between the hops.  The functions below model both schedules with the same
# alpha-beta constants so the dryrun and pipeline_bench can put the exposed
# wire time of each next to the other.

def ring_wire_costs(payload_words: float, axis_sizes) -> tuple[float, float]:
    """(messages, words) on the critical path of ONE dimension-wise ring
    all-reduce of ``payload_words``: per mesh axis of size P > 1,
    ``2 (P - 1)`` collective-permute hops moving ``2 payload (P - 1)/P``
    words (reduce-scatter + all-gather of 1/P-size chunks); size-1 axes are
    free.  The hop count is exactly engine.ring_hops' affine ``(2, -2)`` law
    the analysis sweep machine-verifies against the lowered HLO."""
    L = sum(2 * (P - 1) for P in axis_sizes)
    W = sum(2 * payload_words * (P - 1) / P for P in axis_sizes if P > 1)
    return float(L), float(W)


def psum_wire_time(machine: MachineModel, payload_words: float, P: int) -> float:
    """Serial tree all-reduce: the wire term of the Theorem 6/7 rows."""
    return (machine.alpha * 2 * _logp(P)
            + machine.beta * payload_words * _logp(P))


def ring_wire_time(machine: MachineModel, payload_words: float,
                   axis_sizes) -> float:
    """End-to-end time of the decomposed ring reduction (no overlap credit;
    that is ``pipeline_schedule``'s job)."""
    L, W = ring_wire_costs(payload_words, axis_sizes)
    return machine.alpha * L + machine.beta * W


def pipeline_schedule(machine: MachineModel, *, d: int, n: int, axis_sizes,
                      b: int, s: int, tenants: int = 1,
                      formulation: str = "primal", guard: bool = False,
                      fma: float = 2.0) -> dict:
    """Alpha-beta-gamma model of ONE outer step under both wire schedules.

    The overlappable work per outer step is the step's own compute -- the
    shared Gram contraction (issued one step ahead by the pipelined scan) plus
    the T tenants' sweeps and deferred updates -- so the ring hides
    ``t_hidden = min(t_compute, t_wire_ring)`` of its wire and exposes the
    rest; the monolithic psum exposes ALL of its wire by construction.

    ``fma=2.0`` converts the Theorem-style cell counts (one per multiply-add)
    to hardware flops, since machine peaks (e.g. 197 TFLOP/s) count the FMA
    as two -- without it every compute time would be understated 2x against
    the wire terms.

    Returns a dict with ``payload_words``, ``hops``, ``t_compute``,
    ``t_wire_psum``, ``t_wire_ring``, ``t_hidden``, ``t_exposed_ring``,
    ``t_exposed_psum``, ``overlap_ratio`` (hidden/total ring wire, in
    [0, 1]), and ``step_speedup`` (serial-psum step over pipelined step).
    """
    axis_sizes = tuple(int(P) for P in axis_sizes)
    P = math.prod(axis_sizes)
    sb = s * b
    payload = sb * sb + tenants * sb
    if guard:
        from .engine import _HEALTH_WORDS
        payload += _HEALTH_WORDS
    # one outer step == the H=s slice of the batched critical path
    F_step = batched_costs(d, n, P, b, s, s, tenants, formulation).flops
    t_compute = machine.gamma * fma * F_step
    t_psum = psum_wire_time(machine, payload, P)
    t_ring = ring_wire_time(machine, payload, axis_sizes)
    t_hidden = min(t_compute, t_ring)
    ratio = t_hidden / t_ring if t_ring > 0 else 1.0
    t_step_serial = t_compute + t_psum
    t_step_pipe = max(t_compute, t_ring)
    return {
        "payload_words": float(payload),
        "hops": float(ring_wire_costs(payload, axis_sizes)[0]),
        "t_compute": t_compute,
        "t_wire_psum": t_psum,
        "t_wire_ring": t_ring,
        "t_hidden": t_hidden,
        "t_exposed_ring": t_ring - t_hidden,
        "t_exposed_psum": t_psum,
        "overlap_ratio": ratio,
        "step_speedup": t_step_serial / t_step_pipe if t_step_pipe else 1.0,
    }


def overlap_ratio(machine: MachineModel, *, d: int, n: int, axis_sizes,
                  b: int, s: int, tenants: int = 1,
                  formulation: str = "primal", guard: bool = False) -> float:
    """Fraction of the ring reduction's wire time hidden behind compute --
    the acceptance number pipeline_bench records.  Latency-bound single-
    tenant cells sit near 0 (there is almost no compute to hide behind 60
    hops); the batched serving point is where the schedule pays."""
    return pipeline_schedule(machine, d=d, n=n, axis_sizes=axis_sizes, b=b,
                             s=s, tenants=tenants, formulation=formulation,
                             guard=guard)["overlap_ratio"]


# --------------------------------------------------------------------------
# Per-device HBM traffic of the Gram-packet hot path (the gather term)
# --------------------------------------------------------------------------
# The alpha-beta-gamma model above counts inter-device words (W); on TPU the
# on-device roofline is governed by HBM bytes instead, and the dominant term
# of one outer iteration is how often the sampled sb x n panel crosses HBM.
#
# Both Gram kernels stream their row/column operand tiles from HBM once per
# grid cell, so with B = ceil(sb/bm) row blocks the Gram contraction itself
# reads the panel's worth of rows B times (B^2 cells x bm rows each, halved
# by the symmetric skip but doubled by the two operand panels).  On top of
# that:
#
# materialized baseline (PR 1): Y = X[flat, :] built before the kernel
#     read X rows (gather) + write Y + B x read Y (Gram) + read Y (apply)
#     -> B + 3 panel crossings.
# panel-free (gram_packet_sampled / panel_apply): the kernel gathers rows
#     straight to VMEM -> B x read X rows (Gram) + read X rows (apply), no
#     materialized panel -> B + 1 crossings.
#
# The win is exactly the gather write + gather read + one re-read that the
# fused kernel skips: ratio (B+1)/(B+3), i.e. ~1/2 at the solvers' operating
# points (sb <= bm=128 => B=1) and fading as sb/bm grows -- which is why the
# tuning table keeps bm at the sb it can afford in VMEM.
#
# Column-major gather (layout="cols", the sharded dual's shard operand): the
# kernel fetches each sampled column as a lane-aligned (bk x LANE) slab of
# the ORIGINAL layout and selects the target lane in VMEM, so every panel
# crossing over-reads by the lane width -- ``lane`` x the useful column bytes
# (worst case: sampled columns sharing a lane group are not deduplicated).
# That amplified per-iteration traffic is what the layout trades for
# dropping the pre-transpose's 2x resident dataset (``dual_operand_tradeoff``
# puts both sides of the trade next to each other; ``make bench-smoke``
# records them).
#
# Shared smaller terms (both schedules): the residual operand u (n), the
# alpha/w tile read+write (2n), the sb x sb Gram + sb residual written once,
# and the sb-vector of updates read back by the apply.

def packet_hbm_bytes(sb: int, n: int, itemsize: int = 4,
                     panel_free: bool = True, bm: int = 128,
                     layout: str = "rows", lane: int = 128) -> float:
    """Modeled HBM bytes of ONE outer iteration's packet + deferred apply.
    ``n`` is the contraction length (operand columns for ``layout="rows"``;
    X's rows d for ``layout="cols"``); ``bm`` is the kernel's sample-tile
    size (pass the tuning-table pick).  ``layout="cols"`` applies the
    lane-slab amplification ``lane`` to the panel-crossing term."""
    if layout not in ("rows", "cols"):
        raise ValueError(f"unknown layout {layout!r}")
    amp = lane if layout == "cols" else 1
    panel = sb * n * amp
    blocks = -(-sb // max(bm, 1))
    shared = 3 * n + sb * sb + 2 * sb
    crossings = (blocks + 1) if panel_free else (blocks + 3)
    return float((crossings * panel + shared) * itemsize)


def packet_traffic_breakdown(sb: int, n: int, itemsize: int = 4,
                             bm: int = 128) -> dict:
    """Both schedules' modeled bytes plus the ratio (the bench-smoke
    baseline records this; (B+1)/(B+3) ~= 1/2 while sb <= bm)."""
    base = packet_hbm_bytes(sb, n, itemsize, panel_free=False, bm=bm)
    fused = packet_hbm_bytes(sb, n, itemsize, panel_free=True, bm=bm)
    return {"baseline_bytes": base, "panel_free_bytes": fused,
            "ratio": fused / base}


def dual_operand_tradeoff(d: int, n: int, sb: int, itemsize: int = 4,
                          bm_rows: int | None = None,
                          bm_cols: int | None = None,
                          lane: int = 128) -> dict:
    """Both sides of the dual-layout trade, per operand strategy:

    * ``pretranspose`` (PRs 2-4): row-gather traffic on ``X.T``, but the
      transposed copy doubles the resident dataset for the whole solve (plus
      the one-time 2 d n transpose crossing, not amortized here).
    * ``colgather`` (PR 5): the original layout stays the only copy; each
      panel crossing pays the ``lane``-slab amplification instead.

    Each schedule is modeled at ITS OWN kernel's tile pick (the tuning-table
    (sb, d, layout) entry unless ``bm_rows``/``bm_cols`` pin them) -- using
    one bm for both would misstate whichever kernel runs different tiles.
    ``resident_bytes`` counts the dataset copies plus the solve's vectors
    (w in R^d, alpha and y in R^n); the bench-smoke baseline records the
    measured XLA figures next to these modeled ones.
    """
    if bm_rows is None or bm_cols is None:
        from repro.kernels.gram import tuning  # keep module import light
        if bm_rows is None:
            bm_rows = tuning.pick_tiles(sb, d, np.float32, layout="rows")[0]
        if bm_cols is None:
            bm_cols = tuning.pick_tiles(sb, d, np.float32, layout="cols")[0]
    vectors = (d + 2 * n) * itemsize
    data = d * n * itemsize
    return {
        "pretranspose": {
            "resident_bytes": float(2 * data + vectors),
            "hbm_bytes_per_iter": packet_hbm_bytes(
                sb, d, itemsize, panel_free=True, bm=bm_rows, layout="rows"),
        },
        "colgather": {
            "resident_bytes": float(data + vectors),
            "hbm_bytes_per_iter": packet_hbm_bytes(
                sb, d, itemsize, panel_free=True, bm=bm_cols, layout="cols",
                lane=lane),
        },
    }


# Per-core VMEM on the target parts (TPU v4/v5e: ~16 MiB).  The plan pass
# (repro.analysis.plan_pass) validates every tuning-table entry and PacketPlan
# against this budget; keep it in the cost model so the modeled and the
# checked footprints come from one place.
VMEM_BYTES_PER_CORE = 16 * 1024 * 1024


_COL_GATHER = 8   # sampled columns in flight: sampled_colmajor.GATHER


def kernel_vmem_bytes(bm: int, bk: int, itemsize: int = 4,
                      layout: str = "rows", lane: int = 128) -> float:
    """Static VMEM footprint of the Gram-packet kernels at (bm, bk) tiles --
    the max over the layout's gram and apply kernels, from their declared
    scratch + block shapes (sampled_kernel.py / sampled_colmajor.py):

    * ``rows`` gram: two gathered (bm, bk) panels + the (bm, bm) G tile +
      the bk-length u tile and bm-length r tile.
    * ``cols`` gram: two extracted (bm, bk) panels + two (GATHER, bk, lane)
      slab buffers (the lane-aligned fetch, GATHER columns in flight) + the
      same G/u/r tiles.
    * apply kernels hold one panel (+ one slab for ``cols``) + the bk/bm
      vector tiles; always <= the gram footprint, kept for completeness.

    No double-buffering multiplier: the gathered panels are scratch (manually
    DMA'd), not pipelined BlockSpec operands.
    """
    if layout not in ("rows", "cols", "dense"):
        raise ValueError(f"unknown layout {layout!r}")
    slab = _COL_GATHER * bk * lane if layout == "cols" else 0
    gram = 2 * bm * bk + 2 * slab + bm * bm + bk + bm
    apply_ = bm * bk + slab + bk + bm
    return float(max(gram, apply_) * itemsize)


def packet_memory_time(sb: int, n: int, hbm_bytes_per_s: float,
                       itemsize: int = 4, panel_free: bool = True,
                       bm: int = 128) -> float:
    """Memory-bound roofline time of one outer iteration (the Gram itself is
    MXU-bound only once n/P is small enough that the packet fits in VMEM)."""
    return packet_hbm_bytes(sb, n, itemsize, panel_free, bm) / hbm_bytes_per_s


def best_s(cost_fn, machine: MachineModel, d: int, n: int, P: int, b: int,
           H: int, s_grid=None) -> tuple[int, float]:
    """min_s T(s): returns (s*, T(s*)).  s=1 recovers the classical algorithm,
    so T(s*) <= T(classical) by construction -- the paper's tuning story."""
    if s_grid is None:
        s_grid = [1, 2, 5, 10, 25, 40, 50, 100, 200, 300, 600, 750, 1000]
    best = (1, float("inf"))
    for s in s_grid:
        if H % s:
            continue
        t = cost_fn(d, n, P, b, H, s).time(machine)
        if t < best[1]:
            best = (s, t)
    return best


def strong_scaling(machine: MachineModel, *, d: int, n: int, b: int, H: int,
                   Ps, s_grid=None) -> dict:
    """Figure 8: fixed problem, growing P.  Returns per-P classical time,
    best-s CA time, the chosen s, and the speedup."""
    out = {"P": [], "t_classical": [], "t_ca": [], "s": [], "speedup": []}
    for P in Ps:
        t1 = bcd_costs(d, n, P, b, H, 1).time(machine)
        s, ts = best_s(bcd_costs, machine, d, n, P, b, H, s_grid)
        out["P"].append(P)
        out["t_classical"].append(t1)
        out["t_ca"].append(ts)
        out["s"].append(s)
        out["speedup"].append(t1 / ts)
    return {k: np.asarray(v) for k, v in out.items()}


def weak_scaling(machine: MachineModel, *, d: int, n_per_P: int, b: int, H: int,
                 Ps, s_grid=None) -> dict:
    """Figure 9: n = n_per_P * P."""
    out = {"P": [], "t_classical": [], "t_ca": [], "s": [], "speedup": []}
    for P in Ps:
        n = n_per_P * P
        t1 = bcd_costs(d, n, P, b, H, 1).time(machine)
        s, ts = best_s(bcd_costs, machine, d, n, P, b, H, s_grid)
        out["P"].append(P)
        out["t_classical"].append(t1)
        out["t_ca"].append(ts)
        out["s"].append(s)
        out["speedup"].append(t1 / ts)
    return {k: np.asarray(v) for k, v in out.items()}

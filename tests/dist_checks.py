"""Multi-device checks run in a subprocess with an 8-device CPU world
(tests/test_distributed.py drives this; the main pytest process keeps 1
device).  Each check asserts internally and exits nonzero on failure."""
import os
import re
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def check_solver_equivalence():
    """Distributed CA solvers == single-device solvers, bit-for-bit blocks."""
    from repro.core import (ca_bcd, ca_bcd_sharded, ca_bdcd, ca_bdcd_sharded,
                            make_solver_mesh, sample_blocks)
    from repro.data import SyntheticSpec, make_regression
    X, y, _ = make_regression(jax.random.key(0),
                              SyntheticSpec("t", d=60, n=200, cond=1e5))
    lam = 1e-3
    mesh = make_solver_mesh(8)
    idx = sample_blocks(jax.random.key(1), 60, 8, 64)
    w_d, al_d = ca_bcd_sharded(mesh, X, y, lam, 8, 8, 64, None, idx=idx)
    r = ca_bcd(X, y, lam, 8, 8, 64, None, idx=idx)
    np.testing.assert_allclose(w_d, r.w, rtol=1e-11, atol=1e-13)
    np.testing.assert_allclose(al_d, r.alpha, rtol=1e-11, atol=1e-13)

    idx2 = sample_blocks(jax.random.key(2), 200, 16, 64)
    w_d2, al_d2 = ca_bdcd_sharded(mesh, X, y, lam, 16, 4, 64, None, idx=idx2)
    r2 = ca_bdcd(X, y, lam, 16, 4, 64, None, idx=idx2)
    np.testing.assert_allclose(w_d2, r2.w, rtol=1e-11, atol=1e-13)
    np.testing.assert_allclose(al_d2, r2.alpha, rtol=1e-11, atol=1e-13)

    # PR 5: the transpose-free column-gather dual operand is
    # iterate-identical to the PR-2..4 pre-transposed operand on the
    # 8-shard row layout (baseline reconstructed outside the engine in
    # tests/_legacy_dual.py -- the shipped DualRidge no longer transposes).
    from _legacy_dual import LegacyPreTransposeDual
    from repro.core import SolverPlan, s_step_solve_sharded

    w_leg, al_leg = s_step_solve_sharded(
        LegacyPreTransposeDual(), SolverPlan(b=16, s=4), mesh, X, y, lam,
        64, None, idx=idx2)
    np.testing.assert_allclose(w_leg, w_d2, rtol=1e-12, atol=1e-14)
    np.testing.assert_allclose(al_leg, al_d2, rtol=1e-12, atol=1e-14)

    # fused packet == unfused (same math, one collective)
    w_f, _ = ca_bcd_sharded(mesh, X, y, lam, 8, 8, 64, None, idx=idx,
                            fuse_packet=False)
    np.testing.assert_allclose(w_f, w_d, rtol=1e-12, atol=1e-14)

    # ragged tail: iters % s != 0 runs a final outer iteration with the
    # remainder blocks through the same engine body -- distributed and
    # single-device agree, and both agree with the classical schedule.
    from repro.core import bcd
    idx3 = sample_blocks(jax.random.key(3), 60, 8, 30)
    w_r, al_r = ca_bcd_sharded(mesh, X, y, lam, 8, 8, 30, None, idx=idx3)
    r_loc = ca_bcd(X, y, lam, 8, 8, 30, None, idx=idx3)
    r_cl = bcd(X, y, lam, 8, 30, None, idx=idx3)
    np.testing.assert_allclose(w_r, r_loc.w, rtol=1e-11, atol=1e-13)
    np.testing.assert_allclose(al_r, r_loc.alpha, rtol=1e-11, atol=1e-13)
    np.testing.assert_allclose(w_r, r_cl.w, rtol=1e-11, atol=1e-13)
    # padding path: d=60, n=200 not divisible by 8 -> padded internally (dual)

    # proximal (elastic-net) formulation: the soft-threshold runs on the
    # replicated post-reduce packet, so sharded == local iterates (ragged s
    # included) with the l1 term active and real zeros in the result.
    from repro.core import ca_proximal_bcd, ca_proximal_bcd_sharded
    lam1 = 0.1 * float(np.max(np.abs(X @ y)) / 200)
    w_p, al_p = ca_proximal_bcd_sharded(mesh, X, y, lam, 8, 8, 30, None,
                                        idx=idx3, lam1=lam1)
    r_p = ca_proximal_bcd(X, y, lam, 8, 8, 30, None, idx=idx3, lam1=lam1)
    np.testing.assert_allclose(w_p, r_p.w, rtol=1e-11, atol=1e-13)
    np.testing.assert_allclose(al_p, r_p.alpha, rtol=1e-11, atol=1e-13)
    assert int(np.sum(np.asarray(w_p) != 0)) < 60, "lam1 must induce zeros"
    print("solver_equivalence OK")


def check_pipelined_wire():
    """The pipelined backend (DESIGN.md section 9) against the psum backend.

    Numerics: the ring decomposition sums each packet chunk along ONE fixed
    ring chain and broadcasts the result verbatim, so all shards see
    bit-identical values (replicated-carry consistency) -- but the chain
    order differs from psum's tree order, so pipelined == psum is an f64
    allclose ~1e-12 claim, NOT bit-for-bit.  That looseness is inherent to
    re-associating a float sum and is exactly what the tolerance documents.
    Checked for every registered formulation, even + ragged iters, single +
    batched drivers.

    Wire: the lowering must carry exactly ``H * ring_hops(mesh)`` collective
    -permutes and ZERO all-reduces -- the kind-pinned ``expect_collectives``
    proves the monolithic psum was replaced, not augmented."""
    from repro.core import (ca_accelerated_bcd_pipelined,
                            ca_accelerated_bcd_sharded, ca_bcd_pipelined,
                            ca_bcd_sharded, ca_bdcd_pipelined,
                            ca_bdcd_sharded, ca_proximal_bcd_pipelined,
                            ca_proximal_bcd_sharded, make_solver_mesh,
                            sample_blocks)
    from repro.data import SyntheticSpec, make_regression
    X, y, _ = make_regression(jax.random.key(0),
                              SyntheticSpec("t", d=60, n=200, cond=1e5))
    lam = 1e-3
    mesh = make_solver_mesh(8)
    pairs = {
        "primal": (ca_bcd_pipelined, ca_bcd_sharded, 60, 8, {}),
        "dual": (ca_bdcd_pipelined, ca_bdcd_sharded, 200, 16, {}),
        "proximal": (ca_proximal_bcd_pipelined, ca_proximal_bcd_sharded,
                     60, 8, {"lam1": 1e-3}),
        "accelerated": (ca_accelerated_bcd_pipelined,
                        ca_accelerated_bcd_sharded, 60, 8, {"beta": 0.5}),
    }
    for iters in (64, 30):                       # even and ragged tails
        for name, (ring, psum, dim, b, kw) in pairs.items():
            idx = sample_blocks(jax.random.key(1), dim, b, iters)
            s = 8 if dim == 60 else 4
            w_r, al_r = ring(mesh, X, y, lam, b, s, iters, None, idx=idx, **kw)
            w_p, al_p = psum(mesh, X, y, lam, b, s, iters, None, idx=idx, **kw)
            np.testing.assert_allclose(w_r, w_p, rtol=1e-12, atol=1e-14)
            np.testing.assert_allclose(al_r, al_p, rtol=1e-12, atol=1e-14)
    print("  single-solve equivalence ok (4 formulations, even+ragged)")

    # batched tenants ride the SAME decomposed reduction
    from repro.core import SolverPlan, TenantBatch, s_step_solve_batched_sharded
    from repro.core.engine import PrimalRidge
    T, d, n, b, s, iters = 5, 60, 200, 4, 2, 6
    ys = jnp.stack([jax.random.normal(k, (n,), X.dtype)
                    for k in jax.random.split(jax.random.key(3), T)])
    batch = TenantBatch(ys=ys, lams=jnp.full((T,), lam, X.dtype))
    idxb = sample_blocks(jax.random.key(4), d, b, iters)
    r_p = s_step_solve_batched_sharded(
        PrimalRidge(), SolverPlan(b=b, s=s, tenants=T), mesh, X, batch,
        iters, None, idx=idxb)
    r_r = s_step_solve_batched_sharded(
        PrimalRidge(), SolverPlan(b=b, s=s, tenants=T, wire="ring"), mesh, X,
        batch, iters, None, idx=idxb)
    np.testing.assert_allclose(r_r.ws, r_p.ws, rtol=1e-12, atol=1e-14)
    np.testing.assert_allclose(r_r.alphas, r_p.alphas, rtol=1e-12, atol=1e-14)
    print("  batched equivalence ok")

    # the declared wire schedule, machine-counted (kind-pinned): exactly
    # H * ring_hops collective-permutes, zero all-reduces, guard included
    from repro.analysis import expect_collectives
    from repro.core.distributed import lower_solver, lower_solver_batched
    from repro.core.engine import ring_hops
    hops = ring_hops((8,))                       # 2P - 2 = 14 on the 1D mesh
    for iters, H in ((16, 2), (12, 2)):          # 12 % 8 -> ragged H = 2
        comp = lower_solver(ca_bcd_pipelined, mesh, 64, 256, lam, 8, 8,
                            iters, unroll=max(iters // 8, 1))
        expect_collectives(comp, H * hops, kinds=("collective-permute",),
                           subject=f"pipelined primal[iters={iters}]")
    comp = lower_solver("accelerated", mesh, 64, 256, lam, 8, 8, 16,
                        unroll=2, backend="pipelined", beta=0.5, guard=True)
    expect_collectives(comp, 2 * hops, kinds=("collective-permute",),
                       subject="pipelined accelerated[guard]")
    comp = lower_solver_batched("primal", mesh, 64, 256, 8, 4, 2, 4,
                                unroll=2, wire="ring")
    expect_collectives(comp, 2 * hops, kinds=("collective-permute",),
                       subject="pipelined batched[T=8]")
    print("  wire schedule ok: H *", hops, "collective-permutes, 0 psum")
    print("pipelined_wire OK")


def check_collective_counts():
    """The paper's latency claim, measured: #collectives drops by exactly s.

    The baseline is the *fused* classical schedule (s=1, one Gram||residual
    packet per iteration), which guarantees exactly one sync per iteration by
    construction on every XLA version.  The unfused schedule keeps the
    paper's two logical reductions as separate operands but packs them into
    one explicit variadic psum, so since PR 3 it is also exactly one
    all-reduce per outer iteration on every XLA build (asserted below).

    Counting rides ``repro.analysis.expect_collectives`` -- the contract
    engine's assertion API over the one shared HLO parser -- which also pins
    the KIND: exactly N all-reduces and zero of anything else on the wire."""
    from repro.analysis import expect_collectives
    from repro.core import ca_bcd_sharded, ca_bdcd_sharded, count_in_compiled, \
        make_solver_mesh
    from repro.core.distributed import lower_solver
    mesh = make_solver_mesh(8)
    iters, s = 16, 8
    cl = lower_solver(ca_bcd_sharded, mesh, 64, 256, 1e-3, 8, 1, iters,
                      fuse_packet=True, unroll=iters)
    ca = lower_solver(ca_bcd_sharded, mesh, 64, 256, 1e-3, 8, s, iters,
                      fuse_packet=True, unroll=iters // s)
    expect_collectives(cl, iters, subject="bcd classical")  # 1 sync/iteration
    expect_collectives(ca, iters // s, subject="ca-bcd")    # 1 sync/outer
    # the factor-of-s latency claim is exactly these two counts

    # unfused baseline: Gram and residual stay separate operands but ride ONE
    # explicit variadic-psum packet (engine.psum_variadic), so the count no
    # longer depends on whether this XLA build runs the all-reduce combiner.
    # Regression for the PR-3 satellite: exactly one all-reduce per outer
    # iteration, same as the fused schedule.
    unf = lower_solver(ca_bcd_sharded, mesh, 64, 256, 1e-3, 8, 1, iters,
                       fuse_packet=False, unroll=iters)
    expect_collectives(unf, iters, subject="bcd classical unfused")
    unf_ca = lower_solver("primal", mesh, 64, 256, 1e-3, 8, s, iters,
                          fuse_packet=False, unroll=iters // s)
    expect_collectives(unf_ca, iters // s, subject="ca-bcd unfused")

    # dual layout too
    cl2 = lower_solver(ca_bdcd_sharded, mesh, 256, 64, 1e-3, 8, 1, iters,
                       fuse_packet=True, unroll=iters, col_sharded=False)
    ca2 = lower_solver(ca_bdcd_sharded, mesh, 256, 64, 1e-3, 8, s, iters,
                       fuse_packet=True, unroll=iters // s, col_sharded=False)
    expect_collectives(cl2, iters, subject="bdcd classical")
    expect_collectives(ca2, iters // s, subject="ca-bdcd")

    # proximal path: exactly 1 all-reduce per outer iteration with the
    # soft-threshold active (lam1 > 0) -- the nonsmooth term runs on the
    # replicated post-reduce packet and must add ZERO communication.
    prox = lower_solver("proximal", mesh, 64, 256, 1e-3, 8, s, iters,
                        fuse_packet=True, unroll=iters // s, lam1=1e-3)
    expect_collectives(prox, iters // s, subject="ca-proximal")
    prox_cl = lower_solver("proximal", mesh, 64, 256, 1e-3, 8, 1, iters,
                           fuse_packet=False, unroll=iters, lam1=1e-3)
    expect_collectives(prox_cl, iters, subject="proximal classical unfused")

    # bandwidth grows ~s per Table 1: CA op moves ~s^2 b^2 vs s * b^2 words
    b_cl = count_in_compiled(cl).operand_bytes
    b_ca = count_in_compiled(ca).operand_bytes
    assert 2 < b_ca / b_cl < 2 * s, (b_cl, b_ca)
    print("collective_counts OK")


def check_collective_counts_pallas():
    """ROADMAP open item: the one-all-reduce-per-outer-iteration claim
    verified on the *kernel-backend* lowering, not just the CPU ref lowering.

    Off-TPU the sampled Gram kernel runs in interpret mode (the kernel body
    is traced into the lowering, so the fused schedule's collective structure
    is the real one); on TPU the same assertion runs against the actual
    ``impl="pallas"`` Mosaic lowering."""
    from repro.analysis import expect_collectives
    from repro.core import ca_bcd_sharded, ca_bdcd_sharded, make_solver_mesh
    from repro.core.distributed import lower_solver
    mesh = make_solver_mesh(8)
    iters, s = 4, 2
    impls = ["pallas_interpret"]
    if jax.default_backend() == "tpu":
        impls.append("pallas")
    else:
        print("collective_counts_pallas: no TPU; impl='pallas' branch skipped")
    for impl in impls:
        ca = lower_solver(ca_bcd_sharded, mesh, 16, 256, 1e-3, 4, s, iters,
                          fuse_packet=True, unroll=iters // s, impl=impl)
        expect_collectives(ca, iters // s, subject=f"ca-bcd[{impl}]")
        ca2 = lower_solver(ca_bdcd_sharded, mesh, 256, 64, 1e-3, 4, s, iters,
                           fuse_packet=True, unroll=iters // s,
                           col_sharded=False, impl=impl)
        expect_collectives(ca2, iters // s, subject=f"ca-bdcd[{impl}]")
    print("collective_counts_pallas OK")


def check_batched_collectives():
    """DESIGN.md section 8 on the wire: a T-tenant batched sharded solve
    emits exactly H = ceil(iters/s) all-reduces INDEPENDENT of T, and the
    per-step payload is sb^2 + T*sb words -- the shared Gram packet is not
    scaled by the tenant axis, only the (T, sb) residual directions are."""
    from repro.analysis import expect_collectives
    from repro.core import collective_summary, make_solver_mesh
    from repro.core.distributed import lower_solver_batched
    mesh = make_solver_mesh(8)
    d, n, b, s = 64, 256, 4, 2
    word = 8                                     # x64 subprocess: f64 wire
    for iters in (4, 3):                         # even and ragged tails
        H = iters // s + (1 if iters % s else 0)
        payload = {}
        for tenants in (1, 8, 64):
            comp = lower_solver_batched(
                "primal", mesh, d, n, tenants, b, s, iters,
                unroll=max(iters // s, 1), dtype=jnp.float64)
            expect_collectives(comp, H,
                               subject=f"batched[T={tenants},iters={iters}]")
            payload[tenants] = collective_summary(comp.as_text()).operand_bytes
        for tenants in (8, 64):
            # per-step payload sb_k^2 + T*sb_k with sb_k = s*b on full steps
            # and rem*b on the ragged tail, so the T-scaled part sums to
            # exactly iters*b words per solve -- the Gram part cancels.
            want = payload[1] + (tenants - 1) * word * iters * b
            assert payload[tenants] == want, (
                f"T={tenants} iters={iters}: wire {payload[tenants]} != "
                f"{want} (Gram part must not scale with T)")
    # the dual's per-tenant Gram scale moves post-reduce: same law holds
    comp = lower_solver_batched("dual", mesh, 256, 64, 16, b, s, 4,
                                unroll=2, dtype=jnp.float64)
    expect_collectives(comp, 2, subject="batched dual[T=16]")
    print("batched_collectives OK")


def check_engine_phases():
    """The engine's named phases in the sharded programs: every working
    instruction of every loop body of the psum and ring drivers (single and
    batched) carries exactly one ``engine.*`` phase, the wire's collectives
    are ``reduce`` work -- except the ring's overlapped next packet, which
    is ``packet`` -- and the shard-local operand layout is ``bind``.  The
    index stream is an argument, as the benchmark passes it: the sampler's
    own loops are not the engine's."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from _phases import work_phases
    from repro.core import (FORMULATIONS, SolverPlan, TenantBatch,
                            make_solver_mesh, s_step_solve_batched_sharded,
                            s_step_solve_sharded)
    from repro.core.hlo_analysis import (engine_phase_map, op_phase,
                                         parse_named_ops)
    mesh = make_solver_mesh(8)
    b, s, iters, f64 = 4, 2, 6, jnp.float64

    def sds(shape, spec, dtype=f64):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def lower(form, wire, d, n, tenants=None):
        xspec, yspec, _ = FORMULATIONS[form].dist_in_specs("shards")
        dim = d if form == "primal" else n
        idx = sds((iters, b), P(), jnp.int32)
        X = sds((d, n), xspec)
        if tenants is None:
            plan = SolverPlan(b=b, s=s, wire=wire)
            return jax.jit(lambda X, y, idx: s_step_solve_sharded(
                form, plan, mesh, X, y, 1e-3, iters, idx=idx)).lower(
                X, sds((n,), yspec), idx).compile(), dim
        plan = SolverPlan(b=b, s=s, wire=wire, tenants=tenants)
        return jax.jit(lambda X, ys, lams, idx: s_step_solve_batched_sharded(
            form, plan, mesh, X, TenantBatch(ys, lams), iters, idx=idx)
        ).lower(X, sds((tenants, n), P()), sds((tenants,), P()),
                idx).compile(), dim

    body = {"loop", "packet", "reduce", "assemble", "sweep", "update"}
    programs = {f"{form} {wire}": lower(form, wire, d, n)[0]
                for form, d, n in (("primal", 64, 256), ("dual", 256, 64))
                for wire in ("psum", "ring")}
    programs.update({f"batched {wire}": lower("primal", wire, 64, 256, 4)[0]
                     for wire in ("psum", "ring")})
    for what, comp in programs.items():
        text = comp.as_text()
        counts = work_phases(text, strict=False)
        # The batched init's per-tenant map may be a loop of its own: bind.
        assert body <= set(counts) - {"compiler-made"} <= body | {"bind"}, (
            what, counts)
        assert "bind" in engine_phase_map(text).values(), what
        if what in ("primal ring", "dual ring"):
            # The next step's packet contracted between the ring's phases.
            nested = [op for op in parse_named_ops(text) if re.search(
                r"engine\.reduce/.*engine\.packet", op.op_name or "")]
            assert nested and all(op_phase(op.op_name) == "packet"
                                  for op in nested), what
        print(f"  {what}: {dict(counts)}")
    print("engine_phases OK")


CHECKS = {f.__name__.replace("check_", ""): f for f in
          (check_solver_equivalence, check_pipelined_wire,
           check_collective_counts, check_collective_counts_pallas,
           check_batched_collectives, check_engine_phases)}

if __name__ == "__main__":
    CHECKS[sys.argv[1]]()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (must precede all other imports: the CPU backend reads the device count
# once, when JAX first initializes)

"""The paper's own technique at the production mesh: lower + compile
(CA-)BCD/(CA-)BDCD on 256 chips (16x16, flattened 1D layout over both axes)
and 512 chips (2x16x16), and record the collective schedule per s.

This is hillclimb cell 3 ("most representative of the paper's technique"):
the measured table is
    schedule              syncs / H iters     wire bytes / H iters
    unfused variadic s=1        H               H * (b^2+b) w
    unfused variadic s          H/s             (H/s) * (s^2 b^2 + sb) w
    ours fused s                H/s             (H/s) * (s^2 b^2 + sb) w
(the paper's own schedule would be 2 messages per Gram+residual pair; since
PR 3 the unfused baseline packs both operands into one explicit variadic
psum, so only the wire layout differs from the fused packet).  Solvers are
selected from the (formulation, backend) registry via ``lower_solver``.
Usage: PYTHONPATH=src python -m repro.launch.solver_dryrun [--out DIR]
"""
import argparse
import json
import time

import jax

from repro.core import FORMULATIONS, count_in_compiled
from repro.core.cost_model import TPU_V5E_ICI, pipeline_schedule
from repro.core.distributed import lower_solver, lower_solver_batched


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod ('data', 'model'); multi_pod prepends a
    2-pod DCN axis ('pod', 'data', 'model') = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _overlap_fields(mesh, b: int, s: int, tenants: int = 1,
                    formulation: str = "primal") -> dict:
    """Modeled wire-schedule comparison (DESIGN.md section 9) for one cell:
    what the monolithic psum exposes vs what the pipelined ring hides, on the
    ICI machine model at this mesh's axis sizes."""
    d, n = 4096, 1 << 22
    form = formulation if formulation == "dual" else "primal"
    sch = pipeline_schedule(TPU_V5E_ICI, d=d, n=n,
                            axis_sizes=tuple(mesh.shape[a]
                                             for a in mesh.axis_names),
                            b=b, s=s, tenants=tenants, formulation=form)
    return {"modeled_overlap_ratio": sch["overlap_ratio"],
            "modeled_exposed_psum_s": sch["t_exposed_psum"],
            "modeled_exposed_ring_s": sch["t_exposed_ring"],
            "modeled_ring_hops": sch["hops"]}


def run(out_dir: str = "artifacts/solver", impl: str | None = None,
        formulation: str = "primal") -> list[dict]:
    os.makedirs(out_dir, exist_ok=True)
    results = []
    d, n = 4096, 1 << 22          # dense 4096 x 4.2M f32 panel (64 GiB), abstract
    b, iters = 8, 8
    # The proximal formulation's threshold runs on the replicated post-reduce
    # packet, so its production schedule must be byte-identical to the
    # primal's; lowering it with lam1 > 0 exercises the prox sweep for real.
    solver_kw = {"lam1": 1e-3} if formulation == "proximal" else {}
    for mesh_kind in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        axis = tuple(mesh.axis_names)          # flatten the whole mesh: 1D layout
        for s, fused in ((1, False), (4, False), (4, True), (8, True)):
            if iters % s:
                continue
            t0 = time.time()
            comp = lower_solver(formulation, mesh, d, n, 1e-3, b, s, iters,
                                axis=axis, fuse_packet=fused,
                                unroll=iters // s, impl=impl, **solver_kw)
            cs = count_in_compiled(comp)
            ca = comp.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            rec = {
                "mesh": mesh_kind, "chips": mesh.size, "s": s, "fused": fused,
                "wire": "psum", "formulation": formulation,
                # PacketOperand layout the formulation binds (the dual's
                # "cols" cells lower with NO pre-transpose in the shard body)
                "operand_layout": getattr(FORMULATIONS[formulation],
                                          "operand_layout", "rows"),
                "iters": iters, "collectives": cs.count,
                "operand_bytes": cs.operand_bytes, "link_bytes": cs.link_bytes,
                "flops_per_device": ca.get("flops", 0.0),
                "compile_s": round(time.time() - t0, 1),
                **_overlap_fields(mesh, b, s, formulation=formulation),
            }
            results.append(rec)
            print(f"[solver-dryrun] {mesh_kind} s={s} fused={fused}: "
                  f"{cs.count} collectives / {iters} iters, "
                  f"{cs.operand_bytes:.2e} B wire, "
                  f"compile {rec['compile_s']}s", flush=True)
        # The pipelined backend's ring cell at the best-s point: same packet,
        # the reduction decomposed into collective-permute hops so the next
        # step's Gram contraction overlaps the wire (DESIGN.md section 9).
        s = 8
        t0 = time.time()
        comp = lower_solver(formulation, mesh, d, n, 1e-3, b, s, iters,
                            axis=axis, fuse_packet=True, unroll=iters // s,
                            impl=impl, backend="pipelined", **solver_kw)
        cs = count_in_compiled(comp)
        rec = {
            "mesh": mesh_kind, "chips": mesh.size, "s": s, "fused": True,
            "wire": "ring", "formulation": formulation,
            "operand_layout": getattr(FORMULATIONS[formulation],
                                      "operand_layout", "rows"),
            "iters": iters, "collectives": cs.count,
            "operand_bytes": cs.operand_bytes, "link_bytes": cs.link_bytes,
            "compile_s": round(time.time() - t0, 1),
            **_overlap_fields(mesh, b, s, formulation=formulation),
        }
        results.append(rec)
        print(f"[solver-dryrun] {mesh_kind} s={s} wire=ring: "
              f"{cs.count} collectives / {iters} iters "
              f"(modeled overlap {rec['modeled_overlap_ratio']:.2f}), "
              f"compile {rec['compile_s']}s", flush=True)
    # Keyed by formulation so a proximal dry-run does not clobber the primal
    # artifact ("solver_cells.json" keeps its historical name for primal).
    fname = ("solver_cells.json" if formulation == "primal"
             else f"solver_cells_{formulation}.json")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(results, f, indent=1)
    return results


def run_batched(tenants: int, out_dir: str = "artifacts/solver",
                impl: str | None = None,
                formulation: str = "primal") -> list[dict]:
    """The batched multi-tenant lowering at the production mesh (DESIGN.md
    section 8): T tenant solves, ONE psum per outer step.  Records the
    measured collective schedule (count must equal iters/s regardless of T)
    next to the alpha-beta-gamma model's amortized solves/s and wire
    bytes/iter/tenant, so the dry-run artifact carries both the contract
    and the modeled payoff of the tenant axis."""
    from repro.core.cost_model import (TPU_V5E_ICI, batched_solves_per_second,
                                       tenant_bytes_per_iter)
    os.makedirs(out_dir, exist_ok=True)
    results = []
    d, n = 4096, 1 << 22
    b, iters = 8, 8
    for mesh_kind in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        axis = tuple(mesh.axis_names)
        for s in (1, 4, 8):
            t0 = time.time()
            comp = lower_solver_batched(
                formulation, mesh, d, n, tenants, b, s, iters, axis=axis,
                unroll=iters // s, impl=impl)
            cs = count_in_compiled(comp)
            rec = {
                "mesh": mesh_kind, "chips": mesh.size, "s": s,
                "formulation": formulation, "tenants": tenants,
                "iters": iters, "collectives": cs.count,
                "operand_bytes": cs.operand_bytes, "link_bytes": cs.link_bytes,
                "modeled_solves_per_s": batched_solves_per_second(
                    TPU_V5E_ICI, d=d, n=n, P=mesh.size, b=b, H=iters, s=s,
                    tenants=tenants, formulation=formulation),
                "modeled_bytes_per_iter_per_tenant": tenant_bytes_per_iter(
                    d, n, mesh.size, b, s, tenants, formulation),
                "compile_s": round(time.time() - t0, 1),
                # At serving tenant counts the per-step compute is deep
                # enough to hide most of the ring's wire -- the batched
                # point is where the pipelined schedule pays (section 9).
                **_overlap_fields(mesh, b, s, tenants=tenants,
                                  formulation=formulation),
            }
            results.append(rec)
            print(f"[solver-dryrun] batched {mesh_kind} T={tenants} s={s}: "
                  f"{cs.count} collectives / {iters} iters, "
                  f"{cs.operand_bytes:.2e} B wire, "
                  f"{rec['modeled_solves_per_s']:.1f} modeled solves/s, "
                  f"compile {rec['compile_s']}s", flush=True)
    with open(os.path.join(out_dir,
                           f"solver_cells_batched_T{tenants}.json"), "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="artifacts/solver")
    ap.add_argument("--impl", default=None,
                    help="Gram-packet backend: ref | pallas | pallas_interpret")
    ap.add_argument("--formulation", default="primal",
                    help="registry formulation to lower: primal | dual | "
                         "proximal")
    ap.add_argument("--tenants", type=int, default=None,
                    help="lower the BATCHED multi-tenant solve at this "
                         "tenant-axis width instead of the single-solve cells")
    args = ap.parse_args()
    if args.tenants is not None:
        run_batched(args.tenants, args.out, impl=args.impl,
                    formulation=args.formulation)
    else:
        run(args.out, impl=args.impl, formulation=args.formulation)

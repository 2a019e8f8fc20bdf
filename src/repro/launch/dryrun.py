import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  This module is the ONLY place the 512-device world
# exists; tests and benches see 1 device.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and record what each compile reports.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch ID|all]
        [--shape NAME|all] [--mesh single|multi|both] [--out DIR]
        [--seq-shard-decode true|false]

Per cell this emits artifacts/dryrun/<arch>__<shape>__<mesh>.json with:
  memory_analysis   (per-device argument/output/temp bytes -- proves fit)
  cost_analysis     (per-device HLO flops / bytes accessed)
  collectives       (count + operand/link bytes by kind, parsed from HLO)
  timings           (lower / compile wall seconds)
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.core.hlo_analysis import collective_summary
from repro.launch import inputs as I
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.optim import AdamWConfig
from repro.train.trainer import make_train_step


def _cell_program(cfg, shape, mesh, seq_shard_decode=True):
    """Returns (jitted_fn, abstract_args) for the cell's step program."""
    if shape.kind == "train":
        state, batch = I.train_specs(cfg, shape, mesh)
        step = make_train_step(cfg, AdamWConfig(lr=1e-4), microbatches=1)
        return jax.jit(step, donate_argnums=0), (state, batch)
    if shape.kind == "prefill":
        params, batch = I.prefill_specs(cfg, shape, mesh)

        def prefill_fn(p, b):
            return api.prefill(p, cfg, b, max_seq=shape.seq_len)

        return jax.jit(prefill_fn), (params, batch)
    # decode
    params, cache, tok, pos = I.decode_specs(cfg, shape, mesh,
                                             seq_shard=seq_shard_decode)

    def serve_step(p, c, t, q):
        return api.decode_step(p, cfg, c, t, q)

    return jax.jit(serve_step, donate_argnums=1), (params, cache, tok, pos)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             seq_shard_decode: bool = True, verbose: bool = True,
             overrides: dict | None = None, tag: str = "") -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = shape.applicable(cfg)
    rec = {"arch": cfg.name + tag, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind}
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        _write(rec, out_dir)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    try:
        fn, args = _cell_program(cfg, shape, mesh, seq_shard_decode)
        t0 = time.time()
        with mesh:
            lowered = fn.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        colls = collective_summary(compiled.as_text(), total_devices=n_chips)
        rec.update({
            "status": "ok",
            "chips": n_chips,
            "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "memory_analysis": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "code_bytes": ma.generated_code_size_in_bytes,
            },
            "cost_analysis": {
                "flops_per_device": ca.get("flops", 0.0),
                "bytes_accessed_per_device": ca.get("bytes accessed", 0.0),
            },
            "collectives": {
                "count": colls.count,
                "operand_bytes": colls.operand_bytes,
                "link_bytes": colls.link_bytes,
                "by_kind": {k: {"count": v[0], "operand_bytes": v[1],
                                "link_bytes": v[2]}
                            for k, v in colls.by_kind.items()},
            },
        })
        if verbose:
            print(compiled.memory_analysis())
            print({k: v for k, v in ca.items()
                   if k in ("flops", "bytes accessed")})
    except Exception as e:  # a failing cell is a bug; record and re-raise later
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _write(rec, out_dir)
    return rec


def _parse_set(spec: str | None) -> dict | None:
    """--set k=v[,k=v]: ints, with moe_* keys routed into the MoE config."""
    if not spec:
        return None
    out = {}
    for kv in spec.split(","):
        k, v = kv.split("=")
        out[k] = int(v)
    moe_keys = {k[4:]: v for k, v in out.items() if k.startswith("moe_")}
    out = {k: v for k, v in out.items() if not k.startswith("moe_")}
    if moe_keys:
        out["__moe__"] = moe_keys
    return out


def _apply_overrides(cfg, overrides: dict):
    overrides = dict(overrides)
    moe_keys = overrides.pop("__moe__", None)
    if moe_keys and cfg.moe:
        overrides["moe"] = dataclasses.replace(cfg.moe, **moe_keys)
    return dataclasses.replace(cfg, **overrides)


def _probe_cfg(cfg, depth: int, period: int, shape):
    """Full-width, shallow-depth, fully-unrolled config for exact HLO cost
    counting.  Attention runs single-block (flops-identical: chunking splits
    the same matmuls); the SSD chunk scan and the layer scan are unrolled so
    XLA's cost analysis (which counts while-loop bodies once) sees every op."""
    kw = dict(n_layers=depth, scan_unroll=max(depth // period, 1),
              block_q=shape.seq_len, block_kv=shape.seq_len)
    if cfg.family == "audio":
        kw["enc_layers"] = depth
    if cfg.ssm is not None:
        kw["ssd_unroll"] = max(shape.seq_len // cfg.ssm.chunk, 1)
    return dataclasses.replace(cfg, **kw)


def probe_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
               seq_shard_decode: bool = True, overrides: dict | None = None,
               tag: str = "") -> dict:
    """Two shallow unrolled compiles (depth = 1x and 2x superblock) at full
    width; linear extrapolation gives exact whole-model HLO flops/bytes and
    collective counts/bytes (layer stacks are homogeneous by construction)."""
    from repro.models.api import _superblock_period
    cfg = get_config(arch)
    if overrides:
        cfg = _apply_overrides(cfg, overrides)
    shape = SHAPES[shape_name]
    rec = {"arch": cfg.name + tag, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind, "probe": True}
    ok, why = shape.applicable(cfg)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        _write(rec, out_dir, suffix="__probe")
        return rec
    period = _superblock_period(cfg)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    try:
        points = []
        for depth in (period, 2 * period):
            pcfg = _probe_cfg(cfg, depth, period, shape)
            fn, args = _cell_program(pcfg, shape, mesh, seq_shard_decode)
            with mesh:
                compiled = fn.lower(*args).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            colls = collective_summary(compiled.as_text(),
                                       total_devices=mesh.size)
            points.append({
                "depth": depth,
                "flops": ca.get("flops", 0.0),
                "bytes": ca.get("bytes accessed", 0.0),
                "coll_count": colls.count,
                "coll_operand": colls.operand_bytes,
                "coll_link": colls.link_bytes,
            })
        p1, p2 = points
        blocks = cfg.n_layers // period

        def extrap(key):
            slope = p2[key] - p1[key]           # one superblock's worth
            base = p1[key] - slope              # embed/logits/optimizer
            return max(base + slope * blocks, 0.0), slope, base

        flops, flops_blk, flops_base = extrap("flops")
        byts, _, _ = extrap("bytes")
        cnt, _, _ = extrap("coll_count")
        opnd, _, _ = extrap("coll_operand")
        link, _, _ = extrap("coll_link")
        rec.update({
            "status": "ok", "chips": mesh.size, "points": points,
            "extrapolated_per_device": {
                "flops": flops, "bytes_accessed": byts,
                "coll_count": cnt, "coll_operand_bytes": opnd,
                "coll_link_bytes": link,
                "flops_per_block": flops_blk, "flops_base": flops_base,
            },
        })
    except Exception as e:
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _write(rec, out_dir, suffix="__probe")
    return rec


def _write(rec: dict, out_dir: str, suffix: str = "") -> None:
    os.makedirs(out_dir, exist_ok=True)
    name = (f"{rec['arch'].replace('/', '_')}__{rec['shape']}"
            f"__{rec['mesh']}{suffix}.json")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--seq-shard-decode", default="true")
    ap.add_argument("--probe", action="store_true",
                    help="cost-probe mode (shallow unrolled compiles)")
    ap.add_argument("--set", default=None,
                    help="config override, e.g. q_head_pad=1 (int values)")
    ap.add_argument("--tag", default="", help="artifact name suffix")
    args = ap.parse_args()
    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    seq_shard = args.seq_shard_decode.lower() == "true"

    results = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                t0 = time.time()
                if args.probe:
                    rec = probe_cell(arch, shape, mesh_kind, args.out,
                                     seq_shard, overrides=_parse_set(args.set),
                                     tag=args.tag)
                else:
                    rec = run_cell(arch, shape, mesh_kind, args.out,
                                   seq_shard, overrides=_parse_set(args.set),
                                   tag=args.tag)
                status = rec["status"]
                extra = (f" compile={rec.get('compile_s', 0):.1f}s"
                         if status == "ok" else
                         f" reason={rec.get('reason', rec.get('error', ''))[:120]}")
                print(f"[dryrun] {arch:24s} {shape:12s} {mesh_kind:6s} "
                      f"{status:8s} ({time.time()-t0:.1f}s){extra}", flush=True)
                results.append(rec)

    failed = [r for r in results if r["status"] == "failed"]
    print(f"\n[dryrun] {len(results)} cells: "
          f"{sum(r['status'] == 'ok' for r in results)} ok, "
          f"{sum(r['status'] == 'skipped' for r in results)} skipped, "
          f"{len(failed)} failed")
    if failed:
        for r in failed:
            print(f"  FAILED {r['arch']} {r['shape']} {r['mesh']}: {r['error']}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""Closed loop: one fixed-length solve after another, each ending in
``block_until_ready``, each on its own index stream drawn from the seed.

Entry the window drives: ``repro.core.s_step_solve`` on one chip, with the
operand built once; ``repro.core.s_step_solve_sharded`` over a mesh of
``traffic["shards"]`` chips, X column-sharded, zero columns added so the
shards are equal.

Traffic keys: ``iters`` (per solve), ``shards`` (default 1), ``streams``
(index streams generated in set-up and cycled, default 64),
``check_solves`` (solves compared with the reference, default 3).
"""
from __future__ import annotations

import functools
import gc
import re

from bench import datagen
from bench.loops import (clock, operand_class, reference_fn, rel,
                         sample_dim, span, timed)


class Loop:
    def __init__(self, cfg, traffic, seed, devices, impl):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices, self.impl = devices, impl
        self.shards = traffic.get("shards", 1)
        if len(devices) != self.shards:
            raise ValueError(f"traffic wants {self.shards} chips, the cell "
                             f"has {len(devices)}")
        if self.shards > 1 and cfg["formulation"] != "primal":
            raise ValueError("sharded closed loop: primal column sharding only")
        self.iters = traffic["iters"]

    # ------------------------------------------------------------- set-up --
    def _generators(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        c = self.cfg
        n_pad = (-c["n"]) % self.shards
        gen = functools.partial(datagen.regression, d=c["d"], n=c["n"],
                                cond=c["cond"], noise=c["noise"], n_pad=n_pad)
        dim = sample_dim(c)
        stream = functools.partial(datagen.index_stream, dim=dim, b=c["b"],
                                   iters=self.iters)
        if self.shards == 1:
            return jax.jit(gen), jax.jit(stream), None
        from repro.core import make_solver_mesh
        mesh = make_solver_mesh(self.shards)
        xs = NamedSharding(mesh, P(None, "shards"))
        ys = NamedSharding(mesh, P("shards"))
        rep = NamedSharding(mesh, P())
        return (jax.jit(gen, out_shardings=(xs, ys)),
                jax.jit(stream, out_shardings=rep), mesh)

    def _entry(self, mesh):
        from repro.core import SolverPlan, s_step_solve, s_step_solve_sharded
        c = self.cfg
        plan = SolverPlan(b=c["b"], s=c["s"], impl=self.impl)
        form, lam, iters = c["formulation"], c["lam"], self.iters
        if mesh is None:
            # The whole SolveResult is returned, history included, so the
            # per-iteration metrics the user's call computes stay in the
            # compiled program.
            return lambda op, y, idx: s_step_solve(form, plan, op, y, lam,
                                                   iters, idx=idx)
        return lambda X, y, idx: s_step_solve_sharded(form, plan, mesh, X, y,
                                                      lam, iters, idx=idx)

    def setup(self, run, seconds):
        import jax
        parts = run.setup_parts
        self.gen, gen_idx, mesh = self._generators()
        self.data_key = datagen.subkey(self.seed, "data")
        with timed(parts, "data_s"):
            X, self.y = jax.block_until_ready(self.gen(self.data_key))
            idx_key = datagen.subkey(self.seed, "indices")
            self.streams = [gen_idx(jax.random.fold_in(idx_key, i))
                            for i in range(self.traffic.get("streams", 64))]
            jax.block_until_ready(self.streams)
        with timed(parts, "operand_build_s"):
            if mesh is None:
                self.op = jax.block_until_ready(
                    operand_class(self.cfg["operand"]).of(X))
                del X
            else:
                self.op = X      # the sharded entry lays out its own shard
        entry = self._entry(mesh)
        with timed(parts, "compile_s"):
            self.solve = jax.jit(entry).lower(self.op, self.y,
                                              self.streams[0]).compile()
        hlo = self.solve.as_text()
        run.note(f"compiled solve: pallas kernels in HLO="
                 f"{'tpu_custom_call' in hlo}")
        if mesh is not None:
            ar = len(re.findall(r"\ball-reduce(?:-start)?\(", hlo))
            other = len(re.findall(
                r"\b(?:collective-permute|all-gather|reduce-scatter|all-to-all)"
                r"(?:-start)?\(", hlo))
            trips = -(-self.iters // self.cfg["s"])
            run.note(f"collectives in the compiled solve: all-reduce ops={ar} "
                     f"(in the scan body, {trips} outer steps per solve: "
                     f"{ar * trips} all-reduces), other collectives={other}")
        with timed(parts, "warmup_s"):
            jax.block_until_ready(self.solve(self.op, self.y, self.streams[0]))
        c = self.cfg
        rows = c["s"] * c["b"]
        contraction = (c["n"] + (-c["n"]) % self.shards) // self.shards \
            if c["formulation"] == "primal" else c["d"]
        # Kernel calls per outer step: one packet; the local solve applies
        # its s blocks one by one (it records per-iteration metrics), the
        # sharded one applies all s*b rows at once.
        run.facts.update(
            packet=(rows, contraction),
            apply=(c["b"] if mesh is None else rows, contraction),
            itemsize=4)

    # ------------------------------------------------------------- window --
    def window(self, run, seconds, on_open, on_close):
        import jax
        times, self.outs = [], []
        pool = len(self.streams)
        k = 0
        on_open()
        with span("bench.window"):
            t0 = clock()
            while True:
                t1 = clock()
                with span("bench.solve"):
                    res = jax.block_until_ready(
                        self.solve(self.op, self.y, self.streams[k % pool]))
                t2 = clock()
                times.append(t2 - t1)
                self.outs.append((k % pool, res[0], res[1]))
                k += 1
                if t2 - t0 >= seconds:
                    break
        on_close()
        run.window["solve_s"] = times
        run.attempted = k

    def release(self):
        del self.op, self.solve
        gc.collect()

    # -------------------------------------------------------------- check --
    def check(self, run, limits, control=None):
        """Compare sampled solves of the window with the reference on the
        same data and index streams.  ``control`` puts the reference at that
        matmul mode in the program's place (its readings set the upper end
        of each limit; benchmark runs never pass it)."""
        import numpy as np
        run.failed = sum(
            1 for _, w, a in self.outs
            if not (np.isfinite(np.asarray(w)).all()
                    and np.isfinite(np.asarray(a)).all()))
        rng = datagen.host_rng(self.seed, "check")
        picks = rng.choice(len(self.outs),
                           min(self.traffic.get("check_solves", 3),
                               len(self.outs)), replace=False)
        X, _ = self.gen(self.data_key)
        ref = reference_fn(self.cfg["formulation"], "highest")
        low = reference_fn(self.cfg["formulation"], control) if control else None
        lam = np.float32(self.cfg["lam"])
        w_rel = a_rel = 0.0
        for i in sorted(picks):
            s, w, a = self.outs[i]
            w_ref, a_ref = ref(X, self.y, lam, self.streams[s])
            if low is not None:
                w, a = low(X, self.y, lam, self.streams[s])
            w_rel = max(w_rel, rel(w, w_ref))
            a_rel = max(a_rel, rel(a, a_ref))
        del X
        run.compare("w_rel", w_rel, limits)
        run.compare("alpha_rel", a_rel, limits)
        run.note(f"checked solves {sorted(int(i) for i in picks)} of "
                 f"{len(self.outs)}")

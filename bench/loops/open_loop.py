"""Open loop: the solve service under scheduled arrivals.

Requests arrive on a fixed schedule drawn from the seed (exponential gaps
at ``rate_per_s``, the same set of gaps for every seed in another order),
whether or not the service keeps up.  Each request carries its own target
``y = w_t X + noise`` and a lambda from a log grid around the
configuration's.  The host loop submits every request that is due, then
advances the service by one ``step()``; when nothing is outstanding it
sleeps until the next arrival.  Latency runs from the scheduled arrival to
the ``step()`` that returns the ticket, so a late generator or a stalled
step counts against the service.

Entry the window drives: ``SolverService.submit`` / ``step`` over the
operand built once.

Traffic keys: ``rate_per_s``, ``preroll_s`` (arrivals before the window
opens, so it opens on a loaded service), ``drain_s`` (how long past the
window the loop waits for requests due in it), ``lam_points`` /
``lam_decades`` (the lambda grid), ``service`` (``SolverServiceConfig``
fields), ``warm_to`` (the largest bucket set-up compiles), and
``check_tickets`` (tickets compared with the reference).
"""
from __future__ import annotations

import gc
import math
import time

from bench import datagen
from bench.loops import clock, operand_class, reference_fn, rel, span, timed

_TARGET_BLOCK = 64          # targets generated per device call in set-up


def program_stream(key, dim: int, b: int, iters: int):
    """The service's own index stream for one ``step()``: a copy of
    ``repro.core.sampling.sample_blocks`` (``global_uniform``), which the
    service calls with a key split off its ``PRNGKey(seed)`` chain once per
    step with live requests."""
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(key, iters)
    return jax.vmap(lambda k: jax.random.choice(k, dim, (b,), replace=False)
                    )(keys).astype(jnp.int32)


class Loop:
    def __init__(self, cfg, traffic, seed, devices, impl):
        if len(devices) != 1:
            raise ValueError("the solve service runs on one chip")
        if cfg["formulation"] != "primal":
            raise ValueError("open loop: primal service only so far")
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.impl = impl
        self.svc_cfg = dict(traffic["service"])
        self.svc_seed = seed % (2 ** 31 - 1)
        self.chunks = self.svc_cfg["max_iters"] // self.svc_cfg["chunk_iters"]
        self.steps = 0              # step() calls, each with live requests

    # ------------------------------------------------------------- set-up --
    def _schedule(self, seconds):
        rate = self.traffic["rate_per_s"]
        pre = self.traffic["preroll_s"]
        rng = datagen.host_rng(self.seed, "arrivals")
        n_pre = round(rate * pre)
        n_win = round(rate * seconds)
        t_pre = datagen.arrival_times(n_pre, rate, pre, rng) - pre
        t_win = datagen.arrival_times(n_win, rate, seconds, rng)
        grid = datagen.log_grid(self.cfg["lam"], self.traffic["lam_points"],
                                self.traffic["lam_decades"])
        lams = datagen.host_rng(self.seed, "lams").permutation(
            grid[(datagen.np.arange(n_pre + n_win)) % len(grid)])
        return datagen.np.concatenate([t_pre, t_win]), lams, n_pre

    def _targets(self, X, count):
        """``count`` targets ``w_t X + noise`` (f32, on the host)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        c = self.cfg
        key = datagen.subkey(self.seed, "targets")

        @jax.jit
        def block(X, k):
            k_w, k_e = jax.random.split(k)
            W = jax.random.normal(k_w, (_TARGET_BLOCK, c["d"]), jnp.float32)
            Y = jnp.matmul(W, X, precision=jax.lax.Precision.HIGHEST)
            scale = c["noise"] * jnp.linalg.norm(Y, axis=1, keepdims=True) \
                / math.sqrt(c["n"])
            return Y + scale * jax.random.normal(k_e, Y.shape, jnp.float32)

        out = [np.asarray(block(X, jax.random.fold_in(key, i)))
               for i in range(-(-count // _TARGET_BLOCK))]
        return np.concatenate(out)[:count]

    def setup(self, run, seconds):
        import jax
        from repro.core import SolverPlan
        from repro.serve.solver_service import (SolverService,
                                                SolverServiceConfig)
        c = self.cfg
        parts = run.setup_parts
        self.times, self.lams, self.n_pre = self._schedule(seconds)
        self.data_key = datagen.subkey(self.seed, "data")
        self.gen = jax.jit(lambda k: datagen.regression(
            k, c["d"], c["n"], c["cond"], c["noise"]))
        with timed(parts, "data_s"):
            X, _ = self.gen(self.data_key)
            # The warm-up's requests come first in the pool.
            self.ys = self._targets(X, self.traffic["warm_to"]
                                    + len(self.times))
        with timed(parts, "operand_build_s"):
            op = jax.block_until_ready(operand_class(c["operand"]).of(X))
            del X
        self.svc = SolverService(
            op, SolverPlan(b=c["b"], s=c["s"], impl=self.impl), "primal",
            SolverServiceConfig(seed=self.svc_seed, **self.svc_cfg))
        del op
        self._warm(parts)
        run.facts.update(packet=None, apply=None, itemsize=4)

    def _warm(self, parts):
        """Compile and run every bucket the window uses, through the public
        API: 8 live requests, then 16, and so on up to ``warm_to``, then
        drain.  The service compiles a (chunk, residual) pair per bucket on
        first use; the first ``step()`` at each bucket holds its compile.
        Draining costs ``max_iters / chunk_iters`` steps at the top bucket,
        so ``warm_to`` is the largest bucket the traffic reaches, not the
        table's width."""
        svc, top = self.svc, self.traffic["warm_to"]
        sizes, b = [], self.svc_cfg["min_bucket"]
        while b < top:
            sizes.append(b)
            b *= 2
        sizes.append(top)
        lam = self.cfg["lam"]
        live = 0
        with timed(parts, "compile_s"):
            for size in sizes:
                for _ in range(size - live):
                    svc.submit(self.ys[live], lam)
                    live += 1
                live -= len(self._step())
        with timed(parts, "warmup_s"):
            while live:
                live -= len(self._step())
        self.pool0 = top            # targets before this index were warm-up

    def _step(self):
        with span("bench.step"):
            done = self.svc.step()
        self.steps += 1
        return done

    # ------------------------------------------------------------- window --
    def window(self, run, seconds, on_open, on_close):
        """Arrivals from ``-preroll_s`` to ``seconds``; the window is
        ``[0, seconds)`` of that clock, opened and closed by the callbacks.
        Steps continue after it until every request due in it is done, or
        ``drain_s`` has passed."""
        import numpy as np
        times, n = self.times, len(self.times)
        sched, done_at, late, fin_step = {}, {}, [], {}
        self.tickets = {}
        steps = []                      # (start, end, outstanding)
        drain = self.traffic["drain_s"]
        start = clock() + self.traffic["preroll_s"]
        win = None
        opened = False
        i = 0
        while True:
            now = clock() - start
            if not opened and now >= 0:
                on_open()
                # made after the trace starts, or it records nothing
                win = span("bench.window")
                win.__enter__()
                opened = True
            if opened and win is not None and now >= seconds:
                win.__exit__(None, None, None)
                win = None
                on_close()
            with span("bench.submit"):
                while i < n and times[i] <= now:
                    rid = self.svc.submit(self.ys[self.pool0 + i],
                                          float(self.lams[i]))
                    sched[rid] = i
                    late.append(now - times[i])
                    i += 1
                    now = clock() - start
            outstanding = len(sched) - len(done_at)
            if outstanding:
                t1 = clock() - start
                fin = self._step()
                t2 = clock() - start
                steps.append((t1, t2, outstanding))
                for rid, ticket in fin.items():
                    if rid in sched:
                        done_at[rid] = t2
                        fin_step[rid] = self.steps - 1
                        self.tickets[rid] = ticket
            elif i < n:
                with span("bench.wait"):
                    time.sleep(max(0.0, min(times[i] - now, 0.05)))
            else:
                break
            if now > seconds + drain:
                break
        if win is not None:
            win.__exit__(None, None, None)
            on_close()
        idx_win = {rid for rid, j in sched.items() if j >= self.n_pre}
        lat = [done_at[r] - times[sched[r]] for r in idx_win if r in done_at]
        self.sched, self.fin_step = sched, fin_step
        run.attempted = len(idx_win)
        run.failed = len(idx_win) - len(lat)
        run.window.update(
            latency_s=lat,
            completed_in_window=sum(1 for t in done_at.values()
                                    if 0 <= t < seconds),
            seconds=seconds,
            step_s=[e - s for s, e, _ in steps if 0 <= s and e <= seconds],
            backlog=[(s, o) for s, _, o in steps])
        if lat:
            q = np.percentile(lat, [50, 90, 95, 99]) * 1e3
            run.note(f"latency ms over {len(lat)} requests: p50={q[0]:.1f} "
                     f"p90={q[1]:.1f} p95={q[2]:.1f} p99={q[3]:.1f}")
        lg = np.asarray(late) if late else np.zeros(1)
        run.note(f"arrival generator lateness: mean={lg.mean() * 1e3:.3f} ms "
                 f"p99={np.percentile(lg, 99) * 1e3:.3f} ms "
                 f"max={lg.max() * 1e3:.3f} ms over {len(late)} submits")
        bl = [o for s, _, o in steps if 0 <= s < seconds]
        if bl:
            third = max(1, len(bl) // 3)
            run.note(f"outstanding requests per step in the window: "
                     f"first third mean={np.mean(bl[:third]):.1f} last third "
                     f"mean={np.mean(bl[-third:]):.1f} max={max(bl)}")

    def release(self):
        del self.svc
        gc.collect()

    # -------------------------------------------------------------- check --
    def check(self, run, limits, control=None):
        """Compare sampled tickets with the reference: each ran
        ``max_iters`` iterations over the index streams of the ``step()``
        calls that returned it and the ones before, from zero."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        c = self.cfg
        done = sorted(self.tickets)
        rng = datagen.host_rng(self.seed, "check")
        picks = sorted(rng.choice(len(done), min(
            self.traffic["check_tickets"], len(done)), replace=False))
        keys, key = [], jax.random.PRNGKey(self.svc_seed)
        for _ in range(self.steps):
            key, k = jax.random.split(key)
            keys.append(k)
        stream = jax.jit(lambda k: program_stream(
            k, c["d"], c["b"], self.svc_cfg["chunk_iters"]))
        X, _ = self.gen(self.data_key)
        ref = reference_fn("primal", "highest")
        low = reference_fn("primal", control) if control else None
        w_rel, short = 0.0, 0
        for p in picks:
            rid = done[p]
            t = self.fin_step[rid]
            idx = jnp.concatenate([stream(keys[j])
                                   for j in range(t - self.chunks + 1, t + 1)])
            j = self.sched[rid]
            y, lam = self.ys[self.pool0 + j], np.float32(self.lams[j])
            w_ref, _ = ref(X, y, lam, idx)
            ticket = self.tickets[rid]
            w = low(X, y, lam, idx)[0] if low else ticket.w
            w_rel = max(w_rel, rel(w, w_ref))
        short = sum(1 for t in self.tickets.values()
                    if t.iters != self.svc_cfg["max_iters"]
                    or not np.isfinite(t.w).all())
        del X
        run.compare("w_rel", w_rel, limits)
        run.compare("bad_tickets", float(short), limits)
        run.note(f"checked tickets {[done[p] for p in picks]} of {len(done)}")

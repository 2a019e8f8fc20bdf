"""Continuous-batching front end for the tenant-batched s-step engine.

Built on :class:`~repro.serve.slots.SlotTable`: solve requests (a target
``y``, an l2 weight ``lam``, optional formulation coefficients, a
per-request residual tolerance) queue into free slots, and every
:meth:`step` advances ALL live solves by one chunk of iterations through
ONE :func:`~repro.core.s_step_solve_batched` call -- one scan, one Gram
packet per outer step, shared by every tenant in the chunk.

Compile discipline: the live tenants are gathered into a power-of-two
bucket (padded rows ride inactive, masked to no-ops), and each
``(bucket, formulation)`` pair traces and compiles exactly once -- a service
processing thousands of requests touches O(log slots) lowered shapes total.
The shared operand is built once, in the formulation's kernel layout, and
handed to every compiled chunk as an argument: a closed-over array would be
baked into the program as a constant, and a raw one would be re-laid-out on
every chunk.

Retirement is two-level, matching DESIGN.md section 8:

  * in-chunk: the engine's ``active0`` mask freezes tenants that were
    already retired, bit-exactly (a frozen tenant's carry is untouched);
  * between chunks: the host thresholds each tenant's ``residual`` metric
    against that REQUEST's own tolerance and frees the slot, so a converged
    solve stops consuming sweep work while its neighbors keep iterating.

Tracing: :meth:`SolverService.step` writes host spans into the JAX
profiler's trace when one is being recorded (``solver_service.step`` and,
nested in it, ``.admit``, ``.sample``, ``.chunk``, ``.copy_out``,
``.residual``, ``.retire``), on the clock of the device planes; without a
profiler a span costs one no-op call.  :meth:`SolverService.stats` counts
steps, requests, compiled-function cache misses and the bytes copied between
host and device.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (SolverPlan, TenantBatch, batched_residuals,
                        s_step_solve_batched, sample_blocks)
from repro.core.engine import _resolve_form
from repro.kernels.gram import ColMajorOperand, RowMajorOperand
from repro.serve.slots import SlotTable, bucket_pow2

# The gather operand each formulation layout binds (built once per service).
_OPERANDS = {"rows": RowMajorOperand, "cols": ColMajorOperand}


def _span(phase: str):
    """The host span ``solver_service.<phase>`` in the profiler's trace."""
    return jax.profiler.TraceAnnotation(f"solver_service.{phase}")


@dataclasses.dataclass
class SolverServiceConfig:
    slots: int = 64             # table width == max concurrent tenants
    min_bucket: int = 8         # smallest compiled tenant bucket
    chunk_iters: int = 32       # iterations advanced per step()
    max_iters: int = 1024       # hard per-request cap (no-tol requests stop here)
    tol: float | None = None    # default per-request tolerance (None: run to cap)
    seed: int = 0               # block-index stream seed


@dataclasses.dataclass
class SolveTicket:
    """What a finished request leaves behind."""
    w: np.ndarray
    alpha: np.ndarray
    iters: int
    residual: float
    converged: bool             # True: hit its tolerance; False: iteration cap


class SolverService:
    """Slot-based many-tenant solve server over one shared operand ``X`` (a
    raw (d, n) array, or the formulation's prebuilt operand)."""

    def __init__(self, X: jax.Array, plan: SolverPlan,
                 formulation: str = "primal",
                 cfg: SolverServiceConfig | None = None):
        cfg = cfg or SolverServiceConfig()
        if cfg.min_bucket > cfg.slots:
            raise ValueError(
                f"min_bucket {cfg.min_bucket} exceeds slots {cfg.slots}")
        if plan.tenants is not None:
            raise ValueError(
                "SolverPlan.tenants is pinned by the service per bucket; "
                "pass a plan with tenants=None")
        self.plan = plan
        self.formulation = formulation
        self.form = _resolve_form(formulation)
        kind = _OPERANDS.get(self.form.operand_layout)
        self.X = kind.of(X) if kind is not None else X
        self.cfg = cfg
        self.table = SlotTable(cfg.slots)
        d, n = X.shape
        self.d, self.n = d, n
        dt = X.dtype
        # Per-slot tenant state (numpy: host-mutable between chunks).
        self.ys = np.zeros((cfg.slots, n), dt)
        self.lams = np.ones((cfg.slots,), dt)
        self.coeffs: dict[str, np.ndarray] = {}
        self.ws = np.zeros((cfg.slots, d), dt)
        self.alphas = np.zeros((cfg.slots, n), dt)
        self.iters_run = np.zeros((cfg.slots,), np.int64)
        self.tols = np.full((cfg.slots,), np.inf)
        self._step = 0
        self._solve_cache: dict[tuple, object] = {}
        self._resid_cache: dict[int, object] = {}
        self._key = jax.random.PRNGKey(cfg.seed)
        self._stats = dict.fromkeys(
            ("steps", "admitted", "retired", "h2d_bytes", "d2h_bytes",
             "chunk_compiles"), 0)

    # ------------------------------------------------------------- intake --
    def submit(self, y, lam: float, *, tol: float | None = None,
               **coeffs) -> int:
        """Queue one solve.  ``coeffs`` are per-tenant formulation fields
        (e.g. ``lam1=`` for the proximal); every request of one service must
        pass the same coefficient names, since they shape the compiled
        batch."""
        y = np.asarray(y, self.X.dtype)
        if y.shape != (self.n,):
            raise ValueError(f"y shape {y.shape} != ({self.n},)")
        if self.table.requests and set(coeffs) != set(self.coeffs):
            raise ValueError(
                f"coefficient names {sorted(coeffs)} differ from the "
                f"service's {sorted(self.coeffs)}; one compiled batch "
                "carries one coefficient set")
        for k in coeffs:
            if k not in self.coeffs:
                self.coeffs[k] = np.zeros((self.cfg.slots,), self.X.dtype)
        return self.table.submit(
            {"y": y, "lam": float(lam),
             "tol": self.cfg.tol if tol is None else float(tol),
             "coeffs": {k: float(v) for k, v in coeffs.items()}})

    # -------------------------------------------------------------- serve --
    def step(self) -> dict[int, SolveTicket]:
        """Admit queued requests, advance every live solve by one chunk,
        retire tenants that hit their tolerance or the iteration cap.
        Returns {rid: ticket} for requests finished this step."""
        with _span("step"):
            self._stats["steps"] += 1
            with _span("admit"):
                self._admit()
            live = self.table.active_slots()
            if not live:
                return {}

            with _span("sample"):
                bucket = bucket_pow2(len(live), self.cfg.min_bucket,
                                     self.cfg.slots)
                rows = (live + [live[0]] * (bucket - len(live)))[:bucket]
                active0 = np.zeros((bucket,), bool)
                active0[:len(live)] = True
                self._key, k = jax.random.split(self._key)
                idx = sample_blocks(k, self.form.sample_dim(self.d, self.n),
                                    self.plan.b, self.cfg.chunk_iters)
            with _span("chunk"):
                ws, alphas = self._chunk_fn(bucket)(
                    self.X, *self._tenants_to_device(rows),
                    self._to_device(active0), idx)
            with _span("copy_out"):
                ws, alphas = self._to_host(ws), self._to_host(alphas)
                self.ws[live] = ws[:len(live)]
                self.alphas[live] = alphas[:len(live)]
                self.iters_run[live] += self.cfg.chunk_iters
            with _span("residual"):
                resid = self._to_host(self._resid_fn(bucket)(
                    self.X, *self._tenants_to_device(rows)))
            with _span("retire"):
                finished = self._retire(live, resid)
            self._step += 1
            return finished

    def _admit(self) -> None:
        for req in self.table.admit():
            s, p = req.slot, req.payload
            self.ys[s] = p["y"]
            self.lams[s] = p["lam"]
            self.tols[s] = np.inf if p["tol"] is None else p["tol"]
            for k in self.coeffs:
                self.coeffs[k][s] = p["coeffs"].get(k, 0.0)
            self.ws[s] = 0.0
            self.alphas[s] = 0.0
            self.iters_run[s] = 0
            self._stats["admitted"] += 1

    def _retire(self, live: list[int], resid: np.ndarray
                ) -> dict[int, SolveTicket]:
        finished: dict[int, SolveTicket] = {}
        for i, s in enumerate(live):
            hit_tol = bool(np.isfinite(self.tols[s])
                           and resid[i] <= self.tols[s])
            capped = self.iters_run[s] >= self.cfg.max_iters
            if not (hit_tol or capped):
                continue
            req = self.table.retire(s)
            ticket = SolveTicket(
                w=self.ws[s].copy(), alpha=self.alphas[s].copy(),
                iters=int(self.iters_run[s]), residual=float(resid[i]),
                converged=hit_tol)
            req.out.append(ticket)
            finished[req.rid] = ticket
            self._stats["retired"] += 1
        return finished

    def _tenants_to_device(self, rows: list[int]) -> tuple:
        """The bucket's rows of tenant state, copied to the device:
        ``(ys, lams, coeffs, (ws, alphas))``."""
        dev = self._to_device
        return (dev(self.ys[rows]), dev(self.lams[rows]),
                {n_: dev(v[rows]) for n_, v in self.coeffs.items()},
                (dev(self.ws[rows]), dev(self.alphas[rows])))

    def _to_device(self, a: np.ndarray) -> jax.Array:
        self._stats["h2d_bytes"] += a.nbytes
        return jnp.asarray(a)

    def _to_host(self, x: jax.Array) -> np.ndarray:
        out = np.asarray(x)
        self._stats["d2h_bytes"] += out.nbytes
        return out

    def stats(self) -> dict[str, int]:
        """Cumulative counts since the service was built: ``steps`` (calls
        of :meth:`step`), requests ``admitted`` to a slot and ``retired``
        from one, ``h2d_bytes`` / ``d2h_bytes`` copied between host and
        device (the tenant state's round trip of every step), and
        ``chunk_compiles`` (cache misses of the jitted chunk and residual
        functions: one of each per bucket)."""
        return dict(self._stats)

    def serve(self, max_steps: int | None = None) -> dict[int, SolveTicket]:
        """Run :meth:`step` until the queue and table drain (or
        ``max_steps``).  Returns every ticket finished along the way."""
        done: dict[int, SolveTicket] = {}
        steps = 0
        while self.table.pending or self.table.any_active:
            if max_steps is not None and steps >= max_steps:
                break
            done.update(self.step())
            steps += 1
        return done

    def result(self, rid: int) -> SolveTicket | None:
        req = self.table.requests[rid]
        return req.out[-1] if req.done and req.out else None

    # ----------------------------------------------------------- compiled --
    def _chunk_fn(self, bucket: int):
        """One jitted chunk advance per (bucket, formulation): the compile
        cache the power-of-two padding exists to keep small."""
        key = (bucket, self.formulation, tuple(sorted(self.coeffs)))
        if key not in self._solve_cache:
            self._stats["chunk_compiles"] += 1
            plan = dataclasses.replace(self.plan, tenants=bucket)
            chunk = self.cfg.chunk_iters

            def fn(X, ys, lams, coeffs, carry0, active0, idx):
                batch = TenantBatch(ys=ys, lams=lams, coeffs=coeffs)
                res = s_step_solve_batched(
                    self.formulation, plan, X, batch, chunk,
                    idx=idx, carry0=carry0, active0=active0)
                return res.ws, res.alphas

            self._solve_cache[key] = jax.jit(fn)
        return self._solve_cache[key]

    def _resid_fn(self, bucket: int):
        key = (bucket, self.formulation, tuple(sorted(self.coeffs)))
        if key not in self._resid_cache:
            self._stats["chunk_compiles"] += 1

            def fn(X, ys, lams, coeffs, carries):
                return batched_residuals(
                    self.formulation, X,
                    TenantBatch(ys=ys, lams=lams, coeffs=coeffs), carries)
            self._resid_cache[key] = jax.jit(fn)
        return self._resid_cache[key]

"""Faults planted under the timed path, for the tests that show the check
catches them.  Each wraps a program entry the loops call."""
import jax.numpy as jnp


def solve_fault(real, kind: str):
    """Wrap ``repro.core.s_step_solve`` / ``s_step_solve_sharded``."""
    def wrapped(*args, idx, **kw):
        if kind == "half_block":
            # half of each sampled block left out: its slots repeat the rest
            h = idx.shape[1] // 2
            idx = jnp.concatenate([idx[:, :h], idx[:, :h]], axis=1)
        res = real(*args, idx=idx, **kw)
        w, alpha = res[0], res[1]
        if kind == "unchanged":
            w, alpha = jnp.zeros_like(w), jnp.zeros_like(alpha)
        elif kind == "altered":
            w = w.at[0].add(0.1 * jnp.linalg.norm(w))
        return (w, alpha) + tuple(res[2:]) if isinstance(res, tuple) and \
            not hasattr(res, "_replace") else res._replace(w=w, alpha=alpha)
    return wrapped


def batched_fault(real, kind: str):
    """Wrap ``s_step_solve_batched`` as the solve service calls it."""
    def wrapped(*args, carry0, active0, **kw):
        res = real(*args, carry0=carry0, active0=active0, **kw)
        ws, alphas = res.ws, res.alphas
        if kind == "unchanged":
            ws, alphas = carry0
        elif kind == "half_batch":
            keep = (jnp.arange(ws.shape[0]) % 2 == 1)[:, None]
            ws = jnp.where(keep, carry0[0], ws)
            alphas = jnp.where(keep, carry0[1], alphas)
        elif kind == "altered":
            ws = ws.at[:, 0].add(0.1 * jnp.linalg.norm(ws, axis=1))
        return res._replace(ws=ws, alphas=alphas)
    return wrapped

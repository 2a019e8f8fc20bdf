"""Everything the benchmark generates from ``--seed``: data, index streams
and arrival schedules.  Kept here so that no change to the program can
change the inputs it is measured on.

The regression generator is a copy of the program's ``make_regression(...,
orthogonal=False)`` path (``src/repro/data/regression.py``): a Gaussian
scaled along its short side by a log-linear singular-value ramp, built on
the device in O(dn), with ``y = w* X + noise``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def root_key(seed: int) -> jax.Array:
    """A typed key from any non-negative seed below 2**64.  ``jax.random.key``
    keeps only the low 32 bits of a large seed, so the high bits are folded
    in: seeds that differ above bit 31 give different keys."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} must be in [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def subkey(seed: int, stream: str) -> jax.Array:
    """An independent key per named stream (data, indices, arrivals...)."""
    h = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little") & 0x7FFFFFFF
    return jax.random.fold_in(root_key(seed), h)


def host_rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32,
                                  sum(stream.encode())])


def _ramp(r: int, cond: float) -> jax.Array:
    return jnp.logspace(0.0, -0.5 * math.log10(cond), r, dtype=jnp.float32)


def regression(key, d: int, n: int, cond: float, noise: float, n_pad: int = 0):
    """(X (d, n + n_pad), y (n + n_pad,)) in f32; the padded columns and
    their targets are zero.  Jit it with the output shardings the cell
    needs: the values do not depend on the sharding."""
    k_x, _, _, k_w, k_e, _ = jax.random.split(key, 6)
    ramp = _ramp(min(d, n), cond)
    G = jax.random.normal(k_x, (d, n), jnp.float32) / jnp.sqrt(
        jnp.float32(max(d, n)))
    X = G * (ramp[:, None] if d <= n else ramp[None, :])
    w_star = jax.random.normal(k_w, (d,), jnp.float32)
    y = jnp.matmul(w_star, X, precision=_HI)
    y = y + noise * jnp.linalg.norm(y) / jnp.sqrt(jnp.float32(n)) * \
        jax.random.normal(k_e, (n,), jnp.float32)
    if n_pad:
        X = jnp.pad(X, ((0, 0), (0, n_pad)))
        y = jnp.pad(y, (0, n_pad))
    return X, y


def index_stream(key, dim: int, b: int, iters: int) -> jax.Array:
    """(iters, b) int32 coordinate blocks, each ``b`` distinct indices in
    ``[0, dim)`` in random order: ``b`` sorted draws from ``[0, dim - b]``
    plus ``0..b-1`` are strictly increasing, then a random permutation."""
    k_draw, k_perm = jax.random.split(key)
    draws = jax.random.randint(k_draw, (iters, b), 0, dim - b + 1)
    blocks = jnp.sort(draws, axis=1) + jnp.arange(b)[None, :]
    order = jnp.argsort(jax.random.uniform(k_perm, (iters, b)), axis=1)
    return jnp.take_along_axis(blocks, order, axis=1).astype(jnp.int32)


def exponential_gaps(count: int, rate: float, rng: np.random.Generator
                     ) -> np.ndarray:
    """``count`` inter-arrival gaps of a Poisson process at ``rate``: the
    exponential distribution's midpoint quantiles, in an order drawn from
    ``rng``.  Every seed gets the same set of gaps, so the offered load is
    the same work in another order."""
    q = (np.arange(count) + 0.5) / count
    return rng.permutation(-np.log1p(-q) / rate)


def arrival_times(count: int, rate: float, span: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``count`` open-loop arrival times in ``[0, span)`` with exponential
    gaps, rescaled so that the last gap ends at ``span``."""
    if count == 0:
        return np.zeros((0,))
    gaps = exponential_gaps(count, rate, rng)
    ends = np.cumsum(gaps)
    return (ends - gaps) * (span / ends[-1])


def log_grid(center: float, points: int, decades: float) -> np.ndarray:
    """``points`` values log-spaced over ``decades`` centred on ``center``."""
    return center * np.logspace(-decades / 2, decades / 2, points)

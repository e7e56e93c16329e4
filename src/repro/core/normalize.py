"""Online normalization (SymED Eq. 1-2): damped-window EWMA / EWMV z-scoring.

The paper standardizes every in-memory point each iteration with the *current*
EWMA/EWMV.  Because the Brownian-bridge residual used by the compressor is
affine-invariant (the mean cancels, the scale divides out), downstream code
never needs the re-standardized segment itself -- only the current (mean, var)
pair.  This module provides:

  * ``ewm_step``       -- one O(1) update of (EWMA, EWMV),
  * ``ewm_scan``       -- full-stream scan, batched over leading axes,
  * ``standardize``    -- z-score with a given (mean, var).

The served compressor and the fleet runtime run ``ewm_step`` inside their
scans.  ``ewm_scan`` is the oracle of the Pallas blocked scan
(``repro.kernels.ops.ewma_scan``), which no served path calls.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

__all__ = ["EwmState", "ewm_init", "ewm_step", "ewm_scan", "standardize"]


class EwmState(NamedTuple):
    """Damped-window normalization state (paper Eq. 1-2)."""

    mean: jax.Array  # EWMA_j
    var: jax.Array   # EWMV_j


def ewm_init(t0: jax.Array) -> EwmState:
    """Paper initialization: EWMA_0 = t_0, EWMV_0 = 1.0."""
    t0 = jnp.asarray(t0, jnp.float32)
    return EwmState(mean=t0, var=jnp.ones_like(t0))


def ewm_step(state: EwmState, t: jax.Array, alpha: float | jax.Array) -> EwmState:
    """One damped-window update.

    EWMA_j = a*t_j + (1-a)*EWMA_{j-1}
    EWMV_j = a*(t_j - EWMA_j)^2 + (1-a)*EWMV_{j-1}

    Note the variance uses the *updated* mean (MacGregor & Harris '93 form used
    by the paper -- Eq. 2 references EWMA_j, not EWMA_{j-1}).
    """
    mean = alpha * t + (1.0 - alpha) * state.mean
    var = alpha * (t - mean) ** 2 + (1.0 - alpha) * state.var
    return EwmState(mean=mean, var=var)


def ewm_scan(
    ts: jax.Array, alpha: float | jax.Array, time_axis: int = -1
) -> Tuple[jax.Array, jax.Array]:
    """EWMA/EWMV over a (batched) stream.

    Args:
      ts: float array ``(..., T)`` (time on ``time_axis``).
      alpha: damping weight in (0, 1].

    Returns:
      (means, vars), same shape as ``ts``: the normalization parameters *after*
      ingesting each point (i.e. the params the sender uses at step j).
    """
    ts = jnp.asarray(ts, jnp.float32)
    ts_t = jnp.moveaxis(ts, time_axis, 0)

    init = ewm_init(ts_t[0])

    def step(state: EwmState, t):
        new = ewm_step(state, t, alpha)
        return new, new

    # Step 0 keeps the paper's init (mean=t0, var=1) -- no update on the first
    # point; updates start with t_1.
    _, tail = jax.lax.scan(step, init, ts_t[1:])
    means = jnp.concatenate([init.mean[None], tail.mean], axis=0)
    vars_ = jnp.concatenate([init.var[None], tail.var], axis=0)
    return jnp.moveaxis(means, 0, time_axis), jnp.moveaxis(vars_, 0, time_axis)


def standardize(x: jax.Array, mean: jax.Array, var: jax.Array, eps: float = 1e-12) -> jax.Array:
    """z-score ``x`` with the damped-window params: (x - EWMA)/sqrt(EWMV)."""
    return (x - mean) * jax.lax.rsqrt(var + eps)

"""Evaluation metrics (paper Sec. 4.1): DTW reconstruction error, compression
rate, dimension-reduction rate.

DTW here is the pure-jnp reference (anti-diagonal wavefront, optionally
Sakoe-Chiba banded).  The Pallas kernel in ``repro.kernels.dtw`` implements the
same recurrence with VMEM-resident diagonals; ``repro.kernels.ops.dtw``
dispatches between them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["dtw_ref", "compression_rate_symed", "compression_rate_abba", "drr"]

_INF = 1e30  # plain Python float: a jnp constant would touch the device at import


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_ref(x: jax.Array, y: jax.Array, band: int | None = None) -> jax.Array:
    """DTW distance between 1-D series (batched on leading axes).

    Local cost (x_i - y_j)^2, accumulated along the optimal warping path;
    returns sqrt of the accumulated cost (as used by ABBA's evaluation).

    Anti-diagonal formulation: diagonal d holds cells (i, d-i).  Recurrence
      D[i,j] = c[i,j] + min(D[i-1,j], D[i,j-1], D[i-1,j-1])
    maps to
      cur[i] = c[i, d-i] + min(prev[i-1], prev[i], prev2[i-1]).

    Args:
      x: (..., N), y: (..., M).
      band: Sakoe-Chiba radius (|i-j| <= band); None = full DTW.  The
        effective radius is clamped to ``max(band, |N - M|)``: any warping
        path from (0, 0) to (N-1, M-1) must leave the diagonal by at least
        the length difference, so a narrower band would make the terminal
        cell unreachable and return the _INF sentinel as if it were a
        distance.
    """
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    n, m = x.shape[-1], y.shape[-1]
    r = max(band, abs(n - m)) if band is not None else max(n, m)

    ii = jnp.arange(n)

    def diag_step(carry, d):
        prev2, prev = carry  # diagonals d-2 and d-1, indexed by i
        jj = d - ii
        valid = (jj >= 0) & (jj < m) & (jnp.abs(ii - jj) <= r)
        yv = jnp.take_along_axis(
            jnp.broadcast_to(y, x.shape[:-1] + (m,)),
            jnp.broadcast_to(jnp.clip(jj, 0, m - 1), x.shape[:-1] + (n,)),
            axis=-1,
        )
        cost = (x - yv) ** 2

        shift = lambda a: jnp.concatenate([jnp.full_like(a[..., :1], _INF), a[..., :-1]], -1)
        best = jnp.minimum(jnp.minimum(shift(prev), prev), shift(prev2))
        # origin cell (0,0) has no predecessor
        best = jnp.where((ii == 0) & (jj == 0), 0.0, best)
        cur = cost + best
        cur = jnp.where(valid, cur, _INF)
        return (prev, cur), None

    prev2 = jnp.full(x.shape, _INF, jnp.float32)
    prev = jnp.full(x.shape, _INF, jnp.float32)
    (prev, cur), _ = jax.lax.scan(
        diag_step, (prev2, prev), jnp.arange(n + m - 1)
    )
    # after the last diagonal (d = n+m-2), cell (n-1, m-1) lives in ``cur``
    total = cur[..., n - 1]
    return jnp.sqrt(total)


def compression_rate_symed(n_pieces: jax.Array, n_points) -> jax.Array:
    """CR_SymED = (bytes(P)/2) / bytes(T)  [paper Eq. 3].

    One 4-byte float is transmitted per piece (the endpoint); raw points are
    4-byte floats, so CR = n/N.  (The one-off 4-byte t0 "hello" is excluded,
    matching the paper's formula; see benchmarks for the +4B variant.)
    ``n_points`` may be a static int or a traced scalar (the streaming
    receiver carries the observed stream length in its state).
    """
    return n_pieces.astype(jnp.float32) / jnp.asarray(n_points, jnp.float32)


def compression_rate_abba(
    n_pieces: jax.Array, k_clusters: jax.Array, n_points: int
) -> jax.Array:
    """CR_ABBA = (bytes(C) + bytes(S)) / bytes(T)  [paper Eq. 3].

    Symbols are 1 byte, centers are two 4-byte floats: (8k + n) / 4N.
    """
    num = 8.0 * k_clusters.astype(jnp.float32) + n_pieces.astype(jnp.float32)
    return num / (4.0 * jnp.float32(n_points))


def drr(n_symbols: jax.Array, n_points) -> jax.Array:
    """Dimension-reduction rate len(S)/len(T) (``n_points`` may be traced)."""
    return n_symbols.astype(jnp.float32) / jnp.asarray(n_points, jnp.float32)

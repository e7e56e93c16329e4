"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function matches its kernel bit-for-bit up to float associativity; the
test suite sweeps shapes/dtypes and asserts allclose between the two.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.metrics import dtw_ref as _dtw_core
from repro.core.normalize import ewm_scan as _ewm_core

__all__ = ["ewma_scan_ref", "kmeans_assign_ref", "dtw_batch_ref"]

_BIG = 1e30  # plain Python float: a jnp constant would touch the device at import


def ewma_scan_ref(ts: jax.Array, alpha) -> tuple[jax.Array, jax.Array]:
    """Oracle for ``ewma.ewma_scan_pallas``: the paper-faithful sequential scan."""
    return _ewm_core(jnp.asarray(ts, jnp.float32), alpha)


def kmeans_assign_ref(
    x: jax.Array, mask: jax.Array, centers: jax.Array, center_active: jax.Array
):
    """Oracle for ``kmeans.kmeans_assign_pallas``."""
    x = jnp.asarray(x, jnp.float32)
    centers = jnp.asarray(centers, jnp.float32)
    d = jnp.sum((x[:, :, None, :] - centers[:, None, :, :]) ** 2, axis=-1)
    d = jnp.where(center_active[:, None, :] > 0, d, _BIG)
    labels = jnp.argmin(d, axis=-1).astype(jnp.int32)
    labels = jnp.where(mask > 0, labels, 0)

    k = centers.shape[1]
    onehot = jax.nn.one_hot(labels, k, dtype=jnp.float32) * mask[..., None]
    sums = jnp.einsum("snk,snd->skd", onehot, x,
                      precision=jax.lax.Precision.HIGHEST)
    counts = jnp.sum(onehot, axis=1)
    return labels, sums, counts


def dtw_batch_ref(x: jax.Array, y: jax.Array, band: int | None = None) -> jax.Array:
    """Oracle for ``dtw.dtw_pallas`` (batched equal-length pairs)."""
    return _dtw_core(x, y, band=band)

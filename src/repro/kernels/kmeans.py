"""Pallas TPU kernel: fused k-means assign + cluster statistics.

One Lloyd half-step for a *batch of independent clustering problems* (SymED
receivers each own one): pairwise squared distances via the MXU-friendly
expansion ``|x|^2 - 2 x.c^T + |c|^2``, masked argmin, and the per-cluster
(sum, count) statistics needed for the center update -- all fused so the
(K, N) distance matrix never leaves VMEM.

Layout: grid = (streams, N tiles), every operand feature-major so pieces and
centers sit on the 128-lane axis and the feature dim D on sublanes:

  * ``xt``   (S, Dp, Np): rows ``< D`` the points, row ``D`` the 0/1 mask,
  * ``ct``   (S, Dp, Kp): rows ``< D`` the centers, row ``D`` the 0/1 activity,
  * labels   (S, 1, Np) i32 and stats (S, Dp, Kp) f32 out.

``Dp = round_up(D + 1, 8)``, so SymED's D=2 piece space pads to one 8-row
sublane tile rather than a 128-lane one.  Because the mask rides in row
``D`` of ``xt``, the one stats matmul ``xt . onehot^T`` yields the per-cluster
sums in rows ``< D`` and the counts in row ``D``.  The distance matrix is
(Kp, bn), so the argmin reduces over sublanes and the labels come out
lane-major, as the (1, bn) rows the output block wants.  The stats block's
index map is constant over the N-tile axis, so it stays in VMEM and
accumulates across tiles.  Both matmuls run at ``Precision.HIGHEST``: the
default TPU matmul rounds f32 operands to bf16, which moves labels.

This is the half-step the resident service's fused table digitize runs
once per Lloyd iteration across the whole slot table
(``core.digitize.masked_kmeans_table`` with ``use_kernel=True``, dispatched
through ``kernels.ops.kmeans_assign``).  Contract note: the kernel zeroes
the labels of masked-out pieces while the jnp reference path leaves the
argmin there, and its sums differ from the reference's in float
association, so the kernel path is allclose-but-not-bitwise -- which is
why ``StreamServer`` defaults ``use_kernel`` to off on CPU, where the
bitwise delta-equivalence battery runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils.jax_compat import tpu_compiler_params

__all__ = ["kmeans_assign_pallas"]

_BIG = 1e30  # plain Python float: jnp constants would be captured by the kernel
_HI = jax.lax.Precision.HIGHEST


def _kernel(xt_ref, ct_ref, lab_ref, stats_ref, *, d):
    jt = pl.program_id(1)
    xt = xt_ref[0]                       # (Dp, bn)
    ct = ct_ref[0]                       # (Dp, Kp)
    kp, bn = ct.shape[1], xt.shape[1]
    feat = jax.lax.broadcasted_iota(jnp.int32, (xt.shape[0], 1), 0) < d
    x = jnp.where(feat, xt, 0.0)
    c = jnp.where(feat, ct, 0.0)
    valid = xt[d:d + 1, :] > 0.0                                   # (1, bn)
    active = jnp.transpose(ct[d:d + 1, :]) > 0.0                   # (Kp, 1)

    x2 = jnp.sum(x * x, axis=0, keepdims=True)                     # (1, bn)
    c2 = jnp.transpose(jnp.sum(c * c, axis=0, keepdims=True))      # (Kp, 1)
    cross = jax.lax.dot_general(
        c, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI)         # (Kp, bn)
    dist = jnp.maximum(x2 - 2.0 * cross + c2, 0.0)
    dist = jnp.where(active, dist, _BIG)

    # argmin over the K sublanes, first index on ties (jnp.argmin's rule)
    kid = jax.lax.broadcasted_iota(jnp.int32, (kp, bn), 0)
    dmin = jnp.min(dist, axis=0, keepdims=True)
    labels = jnp.min(jnp.where(dist == dmin, kid, kp), axis=0, keepdims=True)
    lab_ref[0] = jnp.where(valid, labels, 0)                       # (1, bn)

    onehot = jnp.where((kid == labels) & valid, 1.0, 0.0)          # (Kp, bn)
    stats = jax.lax.dot_general(
        xt, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI)         # (Dp, Kp)

    @pl.when(jt == 0)
    def _():
        stats_ref[0] = jnp.zeros_like(stats_ref[0])

    stats_ref[0] += stats


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_pallas(
    x: jax.Array,
    mask: jax.Array,
    centers: jax.Array,
    center_active: jax.Array,
    *,
    block_n: int = 512,
    interpret: bool = False,
):
    """Fused assign + stats for batched k-means.

    Args:
      x: (S, N, D) points.  mask: (S, N) validity.
      centers: (S, K, D).  center_active: (S, K) validity.

    Returns:
      labels (S, N) i32, sums (S, K, D) f32, counts (S, K) f32 --
      ``new_centers = sums / max(counts, 1)`` where counts > 0.
    """
    x = jnp.asarray(x, jnp.float32)
    s, n, d = x.shape
    k = centers.shape[1]

    dp = _round_up(d + 1, 8)
    kp = _round_up(k, 128)
    bn = min(_round_up(block_n, 128), _round_up(n, 128))
    np_ = _round_up(n, bn)

    def feature_major(v, flag, width):
        rows = jnp.concatenate(
            [jnp.swapaxes(jnp.asarray(v, jnp.float32), 1, 2),
             (flag > 0).astype(jnp.float32)[:, None, :]], axis=1)
        return jnp.pad(rows, ((0, 0), (0, dp - d - 1), (0, width - rows.shape[2])))

    xt = feature_major(x, mask, np_)
    ct = feature_major(centers, center_active, kp)

    labels, stats = pl.pallas_call(
        functools.partial(_kernel, d=d),
        grid=(s, np_ // bn),
        in_specs=[
            pl.BlockSpec((1, dp, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, dp, kp), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, dp, kp), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1, np_), jnp.int32),
            jax.ShapeDtypeStruct((s, dp, kp), jnp.float32),
        ],
        # streams parallel, N tiles sequential (stats accumulate in-place)
        compiler_params=tpu_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(xt, ct)
    sums = jnp.swapaxes(stats[:, :d, :k], 1, 2)
    return labels[:, 0, :n], sums, stats[:, d, :k]


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m

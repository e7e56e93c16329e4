"""Pallas TPU kernel: blocked EWMA/EWMV linear-recurrence scan (paper Eq. 1-2).

The sender's normalization is two chained first-order linear recurrences with
constant decay ``a = 1 - alpha``:

    m_j = a*m_{j-1} + alpha*t_j                 (EWMA)
    w_j = a*w_{j-1} + alpha*(t_j - m_j)^2       (EWMV, uses the updated mean)

TPU adaptation (the paper runs this point-by-point in Python on an IoT node):
a blocked scan.  The grid walks (batch tiles -> sequential time blocks); the
carry (m, w) lives in (bb, 1) VMEM scratch across time blocks.  Within a
block the recurrence is closed-form-expanded over 128-lane chunks:

    y_j = a^{j+1} y_{-1} + sum_{i<=j} a^{j-i} x_i

so each chunk is one MXU product ``x @ T`` with the upper-triangular decay
matrix ``T[i, j] = a^{j-i}`` (built once by the wrapper), plus the carry
term; the sequential dependence is only chunk-to-chunk, over static
lane-aligned slices.  Every power of ``a`` is at most 1, so there is no
dynamic-range limit on ``alpha``.  The product runs at
``Precision.HIGHEST`` (the default TPU matmul rounds f32 operands to bf16).

Initialization matches the paper: m_0 = t_0, w_0 = 1.0 exactly (the first
block's carry is seeded from t_0, and the variance input at j=0 is forced to
``alpha`` so that w_0 = (1-alpha)*1 + alpha = 1).

Off the served path: the service's compressor runs the per-point
``core.normalize.ewm_step`` inside its scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils.jax_compat import VMEM, MemorySpace, tpu_compiler_params

__all__ = ["ewma_scan_pallas", "CHUNK"]

CHUNK = 128
_HI = jax.lax.Precision.HIGHEST


def _chunked_scan(x, tri, a_pow1, carry):
    """First-order recurrence over a (bb, bt) block, 128 lanes at a time.

    y_j = a*y_{j-1} + x_j, carry-in ``carry`` (bb, 1).  Returns
    (ys, carry_out).
    """
    outs = []
    for c in range(x.shape[1] // CHUNK):
        xs = x[:, c * CHUNK:(c + 1) * CHUNK]
        ys = carry * a_pow1 + jax.lax.dot_general(
            xs, tri, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_HI)
        outs.append(ys)
        carry = ys[:, CHUNK - 1:]
    return jnp.concatenate(outs, axis=1), carry


def _ewma_kernel(alpha_ref, tri_ref, apow_ref, ts_ref, mean_ref, var_ref,
                 carry_m, carry_w):
    tb = pl.program_id(1)
    alpha = alpha_ref[0]
    tri = tri_ref[...]
    a_pow1 = apow_ref[...]
    ts = ts_ref[...]

    # seed the carry at the first time block: m_{-1} = t_0, w_{-1} = 1
    @pl.when(tb == 0)
    def _():
        carry_m[...] = ts[:, 0:1]
        carry_w[...] = jnp.ones_like(ts[:, 0:1])

    # ---- EWMA: at global j=0, a*t_0 + alpha*t_0 = t_0 (carry is t_0) ------
    means, m_out = _chunked_scan(alpha * ts, tri, a_pow1, carry_m[...])
    mean_ref[...] = means
    carry_m[...] = m_out

    # ---- EWMV: inputs alpha*(t - m)^2; force w_0 = 1 -----------------------
    xw = alpha * (ts - means) ** 2
    j0 = jax.lax.broadcasted_iota(jnp.int32, xw.shape, 1)
    xw = jnp.where((tb == 0) & (j0 == 0), alpha, xw)
    vars_, w_out = _chunked_scan(xw, tri, a_pow1, carry_w[...])
    var_ref[...] = vars_
    carry_w[...] = w_out


@functools.partial(jax.jit, static_argnames=("block_b", "block_t", "interpret"))
def ewma_scan_pallas(
    ts: jax.Array,
    alpha: float | jax.Array,
    *,
    block_b: int = 256,
    block_t: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Blocked EWMA/EWMV over ``ts`` (B, T). Returns (means, vars).

    B is padded to ``block_b`` rows, T to ``block_t`` (rounded to the
    (8, 128) f32 tile).  Matches ``repro.core.normalize.ewm_scan`` to float
    tolerance on the valid region.
    """
    ts = jnp.asarray(ts, jnp.float32)
    b, t = ts.shape
    bb = min(block_b, _round_up(b, 8))
    bt = _round_up(min(block_t, t), CHUNK)
    bp, tp = _round_up(b, bb), _round_up(t, bt)
    ts_p = jnp.pad(ts, ((0, bp - b), (0, tp - t)))

    alpha_arr = jnp.asarray(alpha, jnp.float32).reshape((1,))
    a = 1.0 - alpha_arr[0]
    idx = jnp.arange(CHUNK)
    gap = (idx[None, :] - idx[:, None]).astype(jnp.float32)
    tri = jnp.where(gap >= 0, a ** jnp.maximum(gap, 0.0), 0.0)
    a_pow1 = (a ** (idx + 1).astype(jnp.float32))[None, :]

    grid = (bp // bb, tp // bt)
    means, vars_ = pl.pallas_call(
        _ewma_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=MemorySpace.SMEM),
            pl.BlockSpec((CHUNK, CHUNK), lambda i, j: (0, 0)),
            pl.BlockSpec((1, CHUNK), lambda i, j: (0, 0)),
            pl.BlockSpec((bb, bt), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bt), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bt), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, tp), jnp.float32),
            jax.ShapeDtypeStruct((bp, tp), jnp.float32),
        ],
        scratch_shapes=[
            VMEM((bb, 1), jnp.float32),
            VMEM((bb, 1), jnp.float32),
        ],
        # batch tiles parallel, time blocks sequential (carry in scratch)
        compiler_params=tpu_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(alpha_arr, tri, a_pow1, ts_p)
    return means[:b, :t], vars_[:b, :t]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m

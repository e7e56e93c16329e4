"""SymED receiver: online digitization via warm-started k-means (paper Alg. 3).

Pieces arrive one at a time.  All state lives in fixed-capacity masked buffers
(XLA-friendly):

  * ``pieces``  (n_max, 2)  raw-space (len, inc) tuples, ``n`` of them valid,
  * ``labels``  (n_max,)    current cluster id per piece (labels of *old*
                            pieces may change -- paper Sec. 4.2),
  * ``centers`` (k_max, 2)  raw-space cluster centers, ``k`` of them active.

Faithful semantics:
  * identity labeling while fewer than ``k_min`` pieces exist (Alg. 3 line 2),
  * clustering happens in standardized+scaled space: coords are
    ``(scl * len/std(len), inc/std(inc))`` (ABBA's scl convention; scl=0
    degenerates to 1D clustering on increments),
  * warm start from previous centers with k = k_old; if the max within-cluster
    variance still exceeds ``tol_s^2`` grow k, seeding the new center with the
    newest piece first and random re-init only after that (Alg. 3 lines 10-17),
  * ``GetTolS``: we use tol_s = tol in standardized space (documented heuristic;
    the paper defers to ABBA's variance test).

The inner distance/assign/update step is exactly what the Pallas
``kmeans_assign`` kernel accelerates; ``repro.kernels.ops`` dispatches between
this jnp reference and the kernel.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "DigitizeWork",
    "DigitizerState",
    "digitizer_delta",
    "digitizer_init",
    "digitizer_step",
    "digitizer_table_step",
    "digitize_pieces",
    "digitize_span",
    "digitize_span_table",
    "masked_kmeans",
    "masked_kmeans_table",
    "max_cluster_variance",
    "scale_coords",
]

_BIG = 1e30  # plain Python float: a jnp constant would touch the device at import
# f32 matmuls at full precision: the TPU default rounds operands to bf16,
# which moves labels off the reference's f32 semantics (a no-op on CPU)
_HI = jax.lax.Precision.HIGHEST


class DigitizerState(NamedTuple):
    pieces: jax.Array   # (n_max, 2) raw (len, inc); len stored as f32
    n: jax.Array        # () int32 -- number of valid pieces
    labels: jax.Array   # (n_max,) int32
    centers: jax.Array  # (k_max, 2) raw space
    k: jax.Array        # () int32 -- number of active centers
    key: jax.Array      # PRNG key for the (rare) random re-init path


class DigitizeWork(NamedTuple):
    """Loop work of one digitize pass: ``()`` leaves for one slot, ``(S,)``
    for a slot table.

    Each ``digitizer_step`` runs one warm-start k-means and then one more
    per k-growth round, ``lloyd_iters`` Lloyd iterations each, so a pass
    runs ``lloyd_iters * (trips + rounds)`` Lloyd half-steps per lane.  A
    slot table runs them over every lane at once for as long as its
    widest span and slowest lane need: the useful share of that is
    ``sum(trips + growing_rounds)`` over the table's
    ``S * (max(trips) + rounds_run)``.

    The per-lane counts are read off the span bounds and the digitizer's
    k after the pass, and a table's loop carries one scalar: with three
    more (S,) buffers in its carries, the TPU compiler moved small loop
    buffers out of fast memory, and every growth round ran slower (a
    table step about 1.5% slower on a TPU v5e).
    """

    trips: jax.Array           # pieces digitized: the lane's span length
    growing_rounds: jax.Array  # k-growth rounds in which the lane grew k
    rounds_run: jax.Array      # k-growth rounds its loop ran (table: the
    #                            shard's rounds, on every lane alike)

    @classmethod
    def zeros(cls, shape=()) -> "DigitizeWork":
        """No work: a pass that digitized nothing."""
        z = jnp.zeros(shape, jnp.int32)
        return cls(z, z, z)


def digitizer_init(n_max: int, k_max: int, key: jax.Array) -> DigitizerState:
    return DigitizerState(
        pieces=jnp.zeros((n_max, 2), jnp.float32),
        n=jnp.zeros((), jnp.int32),
        labels=jnp.zeros((n_max,), jnp.int32),
        centers=jnp.zeros((k_max, 2), jnp.float32),
        k=jnp.zeros((), jnp.int32),
        key=key,
    )


def scale_coords(
    pieces: jax.Array, mask: jax.Array, scl: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """ABBA standardization of piece space.

    Returns (scales, coords): ``coords = pieces * scales`` with
    ``scales = (scl/std(len), 1/std(inc))`` over the active pieces.
    No mean removal (increments keep sign semantics, as in ABBA).
    """
    cnt = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
    m = mask[:, None].astype(jnp.float32)
    mean = jnp.sum(pieces * m, axis=0) / cnt
    var = jnp.sum((pieces - mean) ** 2 * m, axis=0) / cnt
    std = jnp.sqrt(var)
    std = jnp.where(std < 1e-12, 1.0, std)
    scales = jnp.stack([scl / std[0], 1.0 / std[1]])
    return scales, pieces * scales


def masked_kmeans(
    coords: jax.Array,
    mask: jax.Array,
    c_init: jax.Array,
    k: jax.Array,
    iters: int = 10,
) -> Tuple[jax.Array, jax.Array]:
    """Lloyd iterations over masked pieces/centers.

    Args:
      coords: (n_max, 2) scaled piece coordinates.
      mask:   (n_max,) bool -- valid pieces.
      c_init: (k_max, 2) initial centers (rows >= k are ignored).
      k:      () int32 active center count.

    Returns (centers, labels): empty clusters keep their previous position.
    """
    k_max = c_init.shape[0]
    center_active = jnp.arange(k_max) < k

    def lloyd(_, carry):
        centers, _ = carry
        labels, sums, counts = _lloyd_half_step(coords, mask, centers,
                                                center_active)
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), centers
        )
        return new_centers, labels

    centers, labels = jax.lax.fori_loop(
        0, iters, lloyd, (c_init, jnp.zeros(coords.shape[0], jnp.int32))
    )
    return centers, labels


def _lloyd_half_step(
    coords: jax.Array,
    mask: jax.Array,
    centers: jax.Array,
    center_active: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The assign half of one Lloyd iteration, single clustering problem.

    Exactly the op sequence ``kernels.kmeans.kmeans_assign_pallas`` fuses:
    masked pairwise distances (MXU expansion), argmin, and the per-cluster
    (sum, count) statistics.  ``masked_kmeans`` consumes it per lane;
    ``masked_kmeans_table`` either vmaps it (bitwise-identical reference) or
    swaps in the Pallas kernel.

    Returns ``(labels (n,), sums (k_max, 2), counts (k_max,))``.
    """
    k_max = centers.shape[0]
    d = _pairwise_sq_dists(coords, centers)
    d = jnp.where(center_active[None, :], d, _BIG)
    labels = jnp.argmin(d, axis=1).astype(jnp.int32)
    onehot = jax.nn.one_hot(labels, k_max, dtype=jnp.float32)
    onehot = onehot * mask[:, None].astype(jnp.float32)
    counts = jnp.sum(onehot, axis=0)                      # (k_max,)
    sums = jnp.matmul(onehot.T, coords, precision=_HI)    # (k_max, 2)
    return labels, sums, counts


def masked_kmeans_table(
    coords: jax.Array,
    mask: jax.Array,
    c_init: jax.Array,
    k: jax.Array,
    iters: int = 10,
    *,
    use_kernel: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Slot-table batch of independent ``masked_kmeans`` problems.

    Args:
      coords: (S, n_max, 2) scaled piece coordinates per slot.
      mask:   (S, n_max) valid pieces per slot.
      c_init: (S, k_max, 2) initial centers.
      k:      (S,) active center counts.
      use_kernel: route the assign half-step through the fused Pallas
        kernel (``kernels.ops.kmeans_assign``, one ``pallas_call`` over the
        whole table) instead of ``jax.vmap(_lloyd_half_step)``.  The vmapped
        path is bitwise-identical to per-slot ``masked_kmeans``; the kernel
        path matches to float tolerance and zeroes labels of masked pieces
        (parity tested in ``tests/test_kernels.py``), so CPU deployments
        keep ``use_kernel=False``.

    Returns ``(centers (S, k_max, 2), labels (S, n_max))``.
    """
    n_streams, n = coords.shape[0], coords.shape[1]
    k_max = c_init.shape[1]
    center_active = jnp.arange(k_max)[None, :] < k[:, None]   # (S, k_max)

    if use_kernel:
        from repro.kernels import ops as _kops  # deferred: avoids an import
        # cycle (kernels.ref pulls in core modules at import time)

        def half(centers):
            return _kops.kmeans_assign(coords, mask, centers, center_active)
    else:
        def half(centers):
            return jax.vmap(_lloyd_half_step)(coords, mask, centers,
                                              center_active)

    def lloyd(_, carry):
        centers, _ = carry
        labels, sums, counts = half(centers)
        new_centers = jnp.where(
            counts[..., None] > 0,
            sums / jnp.maximum(counts[..., None], 1.0), centers
        )
        return new_centers, labels

    return jax.lax.fori_loop(
        0, iters, lloyd, (c_init, jnp.zeros((n_streams, n), jnp.int32))
    )


def _pairwise_sq_dists(x: jax.Array, c: jax.Array) -> jax.Array:
    """||x_i - c_j||^2 via the MXU-friendly expansion (matches the kernel)."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)         # (n, 1)
    c2 = jnp.sum(c * c, axis=1)[None, :]               # (1, k)
    cross = jnp.matmul(x, c.T, precision=_HI)          # (n, k) -- MXU food
    return jnp.maximum(x2 - 2.0 * cross + c2, 0.0)


def max_cluster_variance(
    coords: jax.Array,
    mask: jax.Array,
    centers: jax.Array,
    labels: jax.Array,
    k: jax.Array,
) -> jax.Array:
    """max_c  sum_{p in c} ||p - center_c||^2 / max(|c| - 1, 1).

    Sample variance per cluster (singletons score 0), maximized over active
    clusters -- the paper's MAXCLUSTERVARIANCE tolerance test.
    """
    k_max = centers.shape[0]
    onehot = jax.nn.one_hot(labels, k_max, dtype=jnp.float32)
    onehot = onehot * mask[:, None].astype(jnp.float32)
    sq = jnp.sum((coords[:, None, :] - centers[None, :, :]) ** 2, axis=-1)  # (n,k)
    per_cluster = jnp.sum(sq * onehot, axis=0)  # (k_max,)
    counts = jnp.sum(onehot, axis=0)
    var = per_cluster / jnp.maximum(counts - 1.0, 1.0)
    active = (jnp.arange(k_max) < k) & (counts > 0)
    return jnp.max(jnp.where(active, var, 0.0))


def _raw_centers(
    pieces: jax.Array, mask: jax.Array, labels: jax.Array, k_max: int
) -> Tuple[jax.Array, jax.Array]:
    """Per-cluster means of the *raw* pieces (de-standardization; also the
    right answer for scl=0 where the scaled len coordinate is degenerate)."""
    onehot = jax.nn.one_hot(labels, k_max, dtype=jnp.float32)
    onehot = onehot * mask[:, None].astype(jnp.float32)
    counts = jnp.sum(onehot, axis=0)
    sums = jnp.matmul(onehot.T, pieces, precision=_HI)
    return sums / jnp.maximum(counts[:, None], 1.0), counts


def digitizer_step(
    state: DigitizerState,
    piece: jax.Array,
    *,
    tol: float,
    scl: float,
    k_min: int,
    k_max_active: int,
    lloyd_iters: int = 10,
) -> Tuple[DigitizerState, jax.Array]:
    """Ingest one (len, inc) piece; return updated state + newest symbol id."""
    n_max, k_cap = state.pieces.shape[0], state.centers.shape[0]
    piece = jnp.asarray(piece, jnp.float32)

    pieces = jax.lax.dynamic_update_slice(
        state.pieces, piece[None, :], (state.n, jnp.int32(0))
    )
    n = state.n + 1
    mask = jnp.arange(n_max) < n

    # --- trivial phase (Alg. 3 line 2): every piece its own cluster --------
    def trivial(key):
        labels = jnp.where(mask, jnp.arange(n_max), 0).astype(jnp.int32)
        m = min(k_cap, n_max)  # static
        centers = jnp.zeros((k_cap, 2), jnp.float32)
        centers = centers.at[:m].set(jnp.where(mask[:m, None], pieces[:m], 0.0))
        return DigitizerState(pieces, n, labels, centers, n, key)

    # --- clustering phase ---------------------------------------------------
    def cluster(key):
        scl_arr = jnp.asarray(scl, jnp.float32)
        scales, coords = scale_coords(pieces, mask, scl_arr)
        c_scaled = state.centers * scales[None, :]
        bound = jnp.asarray(tol, jnp.float32) ** 2
        k_hi = jnp.minimum(jnp.asarray(k_max_active, jnp.int32), n)
        k_o = jnp.maximum(state.k, 1)

        def run(c_init, k):
            c, lab = masked_kmeans(coords, mask, c_init, k, lloyd_iters)
            err = max_cluster_variance(coords, mask, c, lab, k)
            return c, lab, err

        c0, lab0, err0 = run(c_scaled, k_o)

        def cond(carry):
            k, _, _, err, _ = carry
            return (k < k_hi) & (err > bound)

        def body(carry):
            k, c, lab, err, key = carry
            k_new = k + 1
            key, sub = jax.random.split(key)

            # k_old + 1: seed the extra center with the newest piece
            newest = coords[n - 1]
            seeded = jax.lax.dynamic_update_slice(c, newest[None, :], (k, 0))

            # beyond that: random re-init from active pieces
            probs = mask.astype(jnp.float32) / jnp.maximum(jnp.sum(mask), 1)
            idx = jax.random.choice(sub, n_max, shape=(k_cap,), replace=False, p=probs)
            randomed = coords[idx]

            c_init = jnp.where(k_new == k_o + 1, seeded, randomed)
            c2, lab2, err2 = run(c_init, k_new)
            return k_new, c2, lab2, err2, key

        k_fin, c_fin, lab_fin, _, key = jax.lax.while_loop(
            cond, body, (k_o, c0, lab0, err0, key)
        )
        centers_raw, _ = _raw_centers(pieces, mask, lab_fin, k_cap)
        # keep previous raw position for (rare) empty active clusters
        return DigitizerState(pieces, n, lab_fin, centers_raw, k_fin, key)

    new_state = jax.lax.cond(n <= k_min, trivial, cluster, state.key)
    symbol = new_state.labels[n - 1]
    return new_state, symbol


def digitizer_delta(
    prev_n: jax.Array,
    state: DigitizerState,
    symbols_online: jax.Array,
    endpoints: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Symbol delta since ``prev_n`` pieces had been digitized.

    This is the receiver's *wire-out* payload (ABBA-VSM-style downstream
    consumers ingest the symbol stream incrementally): after a digitize pass
    advanced ``state.n`` past ``prev_n``, slot ``i < n_new`` of the returned
    arrays holds the symbol emitted when piece ``prev_n + i`` was first
    digitized and the raw endpoint that piece transmitted on the wire in.

    Returns ``(labels, endpoints, n_new)`` with the arrays padded to
    ``n_max`` (zeros beyond ``n_new``), so concatenating the first ``n_new``
    entries of every delta reproduces ``symbols_online[:n]`` /
    ``endpoints[:n]`` exactly.
    """
    n_max = symbols_online.shape[0]
    idx = jnp.arange(n_max)
    n_new = (state.n - prev_n).astype(jnp.int32)
    src = jnp.minimum(prev_n + idx, n_max - 1)
    live = idx < n_new
    return (
        jnp.where(live, symbols_online[src], 0).astype(jnp.int32),
        jnp.where(live, endpoints[src], 0.0).astype(jnp.float32),
        n_new,
    )


def digitize_span(  # symlint: entry(pair=span/slot, shapes=pair-span-slot)
    state: DigitizerState,
    lengths: jax.Array,
    incs: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    *,
    tol: float,
    scl: float,
    k_min: int,
    k_max_active: int,
    lloyd_iters: int = 10,
) -> Tuple[DigitizerState, jax.Array, DigitizeWork]:
    """Ingest buffer slots ``lo <= idx < hi`` into a resumable digitizer.

    This is the online-receiver primitive: pieces live in the padded wire
    buffers ``lengths``/``incs`` (n_max,), ``state.n`` pieces have already
    been digitized (callers pass ``lo = state.n``), and the span up to ``hi``
    (the pieces that arrived since the last digitize) is scanned through
    ``digitizer_step`` one piece at a time.  ``digitize_pieces`` is the
    ``lo=0`` instantiation, so resuming in any number of spans is
    bitwise-identical to one whole-buffer pass by construction.

    Returns ``(state, symbols, work)`` -- ``symbols`` (n_max,) holds the
    symbol emitted when each span slot arrived (0 outside the span);
    ``work`` counts the loop's trips and k-growth rounds (``DigitizeWork``).

    The loop is a ``lax.while_loop`` over a cursor ``j in [lo, hi)``: the
    trip count is the number of pieces actually in the span, not ``n_max``.
    The previous formulation scanned all ``n_max`` positions with a
    ``lax.cond`` gate -- under ``jax.vmap`` (slot tables, fleet slabs) that
    cond lowers to a select which *runs* the full k-means at every position
    and discards the dead results, making every digitize pass cost
    O(n_max * lloyd) regardless of how few pieces arrived (the
    ``resident_speedup`` < 1 regression).  Per lane the executed
    ``digitizer_step`` sequence is identical, so results stay bitwise-equal;
    under vmap the batched while body is select-masked per lane by jax's
    batching rule, preserving that contract.
    """
    n_max = lengths.shape[0]
    pieces = jnp.stack(
        [lengths.astype(jnp.float32), incs.astype(jnp.float32)], axis=-1
    )

    def cond(carry):
        _, _, j = carry
        return j < hi

    def body(carry):
        st, syms, j = carry
        # dead lanes of a batched loop ride along past hi: clamp their read
        jc = jnp.minimum(j, n_max - 1)
        st2, sym = digitizer_step(
            st, pieces[jc], tol=tol, scl=scl, k_min=k_min,
            k_max_active=k_max_active, lloyd_iters=lloyd_iters,
        )
        return st2, syms.at[jc].set(sym), j + 1

    lo = jnp.asarray(lo, jnp.int32)
    final, symbols, _ = jax.lax.while_loop(
        cond, body, (state, jnp.zeros((n_max,), jnp.int32), lo),
    )
    trips = jnp.maximum(hi - lo, 0)
    grew = _growing_rounds(state, final, trips, k_min)
    return final, symbols, DigitizeWork(trips, grew, grew)


def _growing_rounds(before, after, trips, k_min: int):
    """k-growth rounds of ``trips`` digitizer steps, read off k.

    A trivial-phase step sets ``k = n``, one more each step; a clustering
    step starts from ``max(k, 1)`` and adds one center per growth round.
    So what k gained beyond the trivial steps (and beyond the first
    step's ``max(0, 1)`` when ``k_min`` is 0) it gained in growth rounds.
    """
    n = before.n
    trivial = jnp.maximum(jnp.minimum(n + trips, k_min) - n, 0)
    first = ((n == 0) & (trips > 0)).astype(jnp.int32) if k_min == 0 else 0
    return after.k - before.k - trivial - first


def _select_lanes(pred, new, old):
    """Per-lane select over pytrees with an ``(S,)`` leading axis.

    Mirrors what jax's control-flow batching rules do to a vmapped
    ``cond``/``while_loop`` carry: every leaf keeps ``new`` where ``pred``
    and ``old`` elsewhere (select, not arithmetic -- NaNs in dead lanes
    cannot leak through).
    """
    def sel(a, b):
        return jnp.where(pred.reshape(pred.shape + (1,) * (a.ndim - 1)), a, b)

    return jax.tree.map(sel, new, old)


def digitizer_table_step(
    state: DigitizerState,
    piece: jax.Array,
    live: jax.Array,
    *,
    tol: float,
    scl: float,
    k_min: int,
    k_max_active: int,
    lloyd_iters: int = 10,
    use_kernel: bool = False,
) -> Tuple[DigitizerState, jax.Array, jax.Array]:
    """Slot-table batch of ``digitizer_step``: every lane ingests one piece.

    Semantically ``jax.vmap(digitizer_step)`` with a per-lane ``live`` gate,
    but the k-means inner loop runs as *one* table-level problem
    (``masked_kmeans_table``) so ``use_kernel=True`` can fuse the Lloyd
    assign half-step of every slot into a single ``pallas_call``.  The
    ``use_kernel=False`` path lowers to the same batched ops ``jax.vmap``
    produces (control flow is hand-lowered exactly the way jax's batching
    rules do it: both cond branches computed + per-lane select, while-loop
    with an any() predicate and select-masked carries), keeping end-of-
    stream results bitwise-equal to the per-slot path.  The k-growth loop's
    any() runs over the live clustering lanes alone (``live`` and past the
    trivial phase): every other lane's clustering result is dropped by the
    selects after it, so its rounds would change no bit.

    Args:
      state: DigitizerState with an (S,) leading axis on every leaf.
      piece: (S, 2) one raw (len, inc) piece per lane.
      live:  (S,) bool -- lanes with ``live=False`` pass through unchanged.

    Returns ``(state, symbols (S,), rounds)`` -- symbol 0 for dead lanes;
    ``rounds`` () is the number of k-growth rounds the table's loop ran,
    the most any live clustering lane grew.
    """
    n_streams, n_max = state.pieces.shape[0], state.pieces.shape[1]
    k_cap = state.centers.shape[1]
    piece = jnp.asarray(piece, jnp.float32)

    pieces = jax.vmap(
        lambda p, pc, m: jax.lax.dynamic_update_slice(
            p, pc[None, :], (m, jnp.int32(0)))
    )(state.pieces, piece, state.n)
    n = state.n + 1                                           # (S,)
    mask = jnp.arange(n_max)[None, :] < n[:, None]            # (S, n_max)

    # --- trivial phase (batched): every piece its own cluster --------------
    def trivial():
        labels = jnp.where(mask, jnp.arange(n_max)[None, :], 0).astype(jnp.int32)
        m = min(k_cap, n_max)  # static
        centers = jnp.zeros((n_streams, k_cap, 2), jnp.float32)
        centers = centers.at[:, :m].set(
            jnp.where(mask[:, :m, None], pieces[:, :m], 0.0))
        return DigitizerState(pieces, n, labels, centers, n, state.key)

    # --- clustering phase (batched; the k-means runs table-level) ----------
    def cluster():
        scl_arr = jnp.asarray(scl, jnp.float32)
        scales, coords = jax.vmap(
            lambda p, m: scale_coords(p, m, scl_arr))(pieces, mask)
        c_scaled = state.centers * scales[:, None, :]
        bound = jnp.asarray(tol, jnp.float32) ** 2
        # only a live lane past the trivial phase keeps this result: the
        # others may not grow (k >= 1 > 0), so the any() below skips them
        k_hi = jnp.where(
            live & (n > k_min),
            jnp.minimum(jnp.asarray(k_max_active, jnp.int32), n), 0)   # (S,)
        k_o = jnp.maximum(state.k, 1)

        def run(c_init, k):
            c, lab = masked_kmeans_table(coords, mask, c_init, k, lloyd_iters,
                                         use_kernel=use_kernel)
            err = jax.vmap(max_cluster_variance)(coords, mask, c, lab, k)
            return c, lab, err

        c0, lab0, err0 = run(c_scaled, k_o)

        def growing(k, err):
            return (k < k_hi) & (err > bound)

        def cond(carry):
            k, _, _, err, _ = carry
            return jnp.any(growing(k, err))

        def body(carry):
            k, c, lab, err, key = carry
            grow = growing(k, err)                            # (S,)
            k_new = k + 1
            splits = jax.vmap(jax.random.split)(key)
            key_new, sub = splits[:, 0], splits[:, 1]

            # k_old + 1: seed the extra center with the newest piece
            newest = jnp.take_along_axis(
                coords, (n - 1)[:, None, None], axis=1)[:, 0]  # (S, 2)
            seeded = jax.vmap(
                lambda cc, nw, kk: jax.lax.dynamic_update_slice(
                    cc, nw[None, :], (kk, jnp.int32(0)))
            )(c, newest, k)

            # beyond that: random re-init from active pieces
            probs = mask.astype(jnp.float32) / jnp.maximum(
                jnp.sum(mask, axis=1, keepdims=True), 1)
            idx = jax.vmap(
                lambda s, p: jax.random.choice(
                    s, n_max, shape=(k_cap,), replace=False, p=p)
            )(sub, probs)
            randomed = jnp.take_along_axis(coords, idx[:, :, None], axis=1)

            c_init = jnp.where((k_new == k_o + 1)[:, None, None],
                               seeded, randomed)
            c2, lab2, err2 = run(c_init, k_new)
            return _select_lanes(
                grow, (k_new, c2, lab2, err2, key_new), (k, c, lab, err, key))

        k_fin, c_fin, lab_fin, _, key = jax.lax.while_loop(
            cond, body, (k_o, c0, lab0, err0, state.key)
        )
        # each lane grows in a prefix of the rounds, one center a round
        rounds = jnp.max(k_fin - k_o)
        del c_fin  # raw-space centers are recomputed from the labeling
        centers_raw = jax.vmap(
            lambda p, m, l: _raw_centers(p, m, l, k_cap)[0]
        )(pieces, mask, lab_fin)
        return DigitizerState(pieces, n, lab_fin, centers_raw, k_fin,
                              key), rounds

    trivial_state = trivial()
    clustered, rounds = cluster()
    stepped = _select_lanes(n <= k_min, trivial_state, clustered)
    symbol = jnp.take_along_axis(stepped.labels, (n - 1)[:, None], axis=1)[:, 0]
    new_state = _select_lanes(live, stepped, state)
    return new_state, jnp.where(live, symbol, 0), rounds


def digitize_span_table(  # symlint: entry(pair=span/table, shapes=pair-span-table)
    state: DigitizerState,
    lengths: jax.Array,
    incs: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    *,
    tol: float,
    scl: float,
    k_min: int,
    k_max_active: int,
    lloyd_iters: int = 10,
    use_kernel: bool = False,
) -> Tuple[DigitizerState, jax.Array, DigitizeWork]:
    """Slot-table batch of ``digitize_span``: per-lane spans, shared loop.

    Every lane owns a cursor walking its ``[lo_s, hi_s)`` span; the loop
    runs until every lane drains (trip count = the *widest* span in the
    table, not ``n_max``), each iteration one table-level
    ``digitizer_table_step``.  Lanes whose cursor is done are select-masked
    exactly like a vmapped per-lane while loop, so results are bitwise-equal
    to ``jax.vmap(digitize_span)`` on the reference path while
    ``use_kernel=True`` fuses each iteration's Lloyd half-steps across the
    whole table into single ``pallas_call``s.

    Args:
      state: batched DigitizerState ((S,) leading axis).
      lengths/incs: (S, n_max) padded piece buffers.
      lo/hi: (S,) span bounds per lane (``lo == hi`` lanes are no-ops).

    Returns ``(state, symbols (S, n_max), work)`` -- symbols 0 outside
    each span; ``work`` (``DigitizeWork``, (S,) leaves) counts per lane the
    trips it was live in and the growth rounds it grew in, and on every
    lane the growth rounds the table's loop ran.  A lane's trips and
    growing rounds equal the per-slot ``digitize_span``'s.
    """
    n_streams, n_max = lengths.shape
    pieces = jnp.stack(
        [lengths.astype(jnp.float32), incs.astype(jnp.float32)], axis=-1
    )

    def cond(carry):
        _, _, j, _ = carry
        return jnp.any(j < hi)

    def body(carry):
        st, syms, j, rounds_run = carry
        live = j < hi                                         # (S,)
        jc = jnp.minimum(j, n_max - 1)
        piece = jnp.take_along_axis(pieces, jc[:, None, None], axis=1)[:, 0]
        st2, sym, rounds = digitizer_table_step(
            st, piece, live, tol=tol, scl=scl, k_min=k_min,
            k_max_active=k_max_active, lloyd_iters=lloyd_iters,
            use_kernel=use_kernel,
        )
        # write each live lane's symbol at its own cursor; dead lanes
        # rewrite their current value (a no-op)
        cur = jnp.take_along_axis(syms, jc[:, None], axis=1)[:, 0]
        syms2 = syms.at[jnp.arange(n_streams), jc].set(
            jnp.where(live, sym, cur))
        return st2, syms2, jnp.where(live, j + 1, j), rounds_run + rounds

    lo = jnp.asarray(lo, jnp.int32)
    final, symbols, _, rounds_run = jax.lax.while_loop(
        cond, body,
        (state, jnp.zeros((n_streams, n_max), jnp.int32), lo, jnp.int32(0)),
    )
    trips = jnp.maximum(hi - lo, 0)
    return final, symbols, DigitizeWork(
        trips, _growing_rounds(state, final, trips, k_min),
        jnp.broadcast_to(rounds_run, trips.shape))


@functools.partial(
    jax.jit,
    static_argnames=("k_cap", "k_min", "k_max_active", "lloyd_iters", "use_kernel"),
)
def digitize_pieces(  # symlint: entry(drive=digitize, budget=0, shapes=digitize-pieces)
    lengths: jax.Array,
    incs: jax.Array,
    n_pieces: jax.Array,
    key: jax.Array,
    *,
    k_cap: int = 100,
    tol: float = 0.5,
    scl: float = 1.0,
    k_min: int = 3,
    k_max_active: int = 100,
    lloyd_iters: int = 10,
    use_kernel: bool = False,  # reserved: kernels.ops dispatch happens above us
) -> dict:
    """Run the receiver over a padded piece sequence (single stream).

    Args:
      lengths/incs: (n_max,) padded piece arrays (receiver-reconstructed).
      n_pieces: () int32 number of valid pieces.

    Returns dict with final ``labels``/``centers``/``k`` plus the per-step
    symbol emission ``symbols`` (n_max,) (symbol assigned when each piece
    arrived; later steps may relabel earlier pieces -- final labeling is
    ``labels``).
    """
    n_max = lengths.shape[0]
    k_cap = int(k_cap)
    state = digitizer_init(n_max, k_cap, key)
    final, symbols, _ = digitize_span(
        state, lengths, incs, jnp.zeros((), jnp.int32), n_pieces,
        tol=tol, scl=scl, k_min=k_min, k_max_active=k_max_active,
        lloyd_iters=lloyd_iters,
    )
    return {
        "labels": final.labels,
        "centers": final.centers,
        "k": final.k,
        "symbols": symbols,
        "state": final,
    }

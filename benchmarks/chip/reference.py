"""Plain per-session SymED: the answer the served symbol stream is held to.

Written from the paper's algorithms (arXiv:2309.03014, Alg. 1-3) in numpy,
one session at a time, with none of the served path's machinery: no slot
table, no padding to ``n_max``, no kernel, no batching across sessions.  It
imports nothing of the program under test; ``jax.random`` is used only as
the definition of the PRNG stream that the digitizer's random re-seeding
draws from (the OPEN frame's u32 seed is a ``jax.random.key``).

``dtype`` sets the precision every arithmetic step rounds to: float32 is
the reference, ``ml_dtypes.bfloat16`` the lower-precision control that the
comparison must reject.  Piece endpoints are raw points copied onto the
wire, so they stay the float32 values sent whatever ``dtype`` computes.

Semantics, as the configuration states them:

* Sender (Alg. 1): damped-window EWMA/EWMV normalization; a segment grows
  one point at a time and closes, excluding the newest point, when its
  Brownian-bridge error in normalized space exceeds ``(len - 2) tol^2`` or
  it would exceed ``len_max`` points.  The closed piece's raw endpoint goes
  on the wire with its arrival step; the stream's last open segment is
  flushed at close.
* Receiver (Alg. 2): ``len_i = step_i - step_{i-1}`` (``step_{-1} = 1``),
  ``inc_i = e_i - e_{i-1}`` (``e_{-1} = t0``).
* Digitizer (Alg. 3): identity labels while at most ``k_min`` pieces exist;
  after that, ABBA-scaled coordinates ``(scl len / std(len), inc /
  std(inc))``, a warm start from the previous centers, ``lloyd_iters`` Lloyd
  iterations, and k grows while the largest within-cluster sample variance
  exceeds ``tol^2`` (first new center: the newest piece; later ones: a
  random re-seed from the pieces).  A piece's symbol is its label right
  after it was digitized.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

__all__ = ["compress", "wire_pieces", "digitize", "encode_session"]

_BIG = 1e30


def compress(ts: np.ndarray, *, tol: float, len_max: int, alpha: float,
             fused: bool, dtype=np.float32) -> Dict[str, np.ndarray]:
    """Run the sender over ``ts`` ``(B, T)`` (rows independent).

    Returns per-step ``emit`` (B, T) bool and ``endpoint`` (B, T), and the
    flush of the open segment at the end: ``tail_emit`` (B,),
    ``tail_endpoint`` (B,).  Step 0 never emits.
    """
    f = lambda v: np.asarray(v, dtype)  # noqa: E731 -- round to ``dtype``
    raw = np.asarray(ts, np.float32)  # what goes on the wire, as sent
    x = f(raw)
    b, t_len = x.shape
    a, one_minus_a = f(alpha), f(1.0) - f(alpha)
    sixth = f(1.0) / f(6.0)
    mean, var = x[:, 0].copy(), np.ones(b, dtype)
    start, last = x[:, 0].copy(), x[:, 0].copy()
    npts = np.ones(b, np.int64)
    s0 = s1 = s2 = np.zeros(b, dtype)
    emit = np.zeros((b, t_len), bool)
    endpoint = np.zeros((b, t_len), np.float32)
    for j in range(1, t_len):
        t = x[:, j]
        mean = a * t + one_minus_a * mean
        var = a * (t - mean) ** 2 + one_minus_a * var
        v = t - start
        h = f(npts)
        g0, g1, g2 = s0 + v, s1 + h * v, s2 + v * v
        n_new = npts + 1
        length = np.maximum(f(n_new) - f(1.0), f(1.0))
        sum_h2 = (length * (length + f(1.0)) * (f(2.0) * length + f(1.0))
                  * sixth)
        r = v / length
        if fused:
            w = np.float64
            part = f(w(g2) - w(f(2.0) * r) * w(g1))
            err = f(w(r * r) * w(sum_h2) + w(part))
        else:
            err = g2 - f(2.0) * r * g1 + r * r * sum_h2
        err = np.maximum(err, f(0.0))
        err = err / np.maximum(var, f(1e-12))
        bound = (f(n_new) - f(2.0)) * f(tol) * f(tol)
        cut = (err > bound) | (n_new > len_max)
        emit[:, j] = cut
        endpoint[:, j] = np.where(cut, raw[:, j - 1], 0.0)
        v1 = t - last
        start = np.where(cut, last, start)
        s0 = np.where(cut, v1, g0)
        s1 = np.where(cut, v1, g1)
        s2 = np.where(cut, v1 * v1, g2)
        npts = np.where(cut, 2, n_new)
        last = t
    return {"emit": emit, "endpoint": endpoint, "tail_emit": npts >= 2,
            "tail_endpoint": raw[:, -1]}


def wire_pieces(comp: Dict[str, np.ndarray], row: int, t_seen: int,
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Endpoints and arrival steps of one session that sent ``t_seen``
    points and then closed: ``comp`` must come from ``compress`` over
    exactly those points (the close flushes the open segment at step
    ``t_seen``)."""
    idx = np.nonzero(comp["emit"][row, :t_seen])[0]
    endpoints = list(comp["endpoint"][row, idx])
    steps = list(idx)
    if comp["tail_emit"][row]:
        endpoints.append(comp["tail_endpoint"][row])
        steps.append(t_seen)
    return np.asarray(endpoints), np.asarray(steps, np.int64)


def _lloyd(coords, centers, k, iters, f):
    """``iters`` Lloyd iterations over all pieces; labels of the last
    assignment, centers after the last update (empty clusters stay)."""
    kc = centers.shape[0]
    active = np.arange(kc) < k
    x2 = (coords * coords).sum(1, dtype=coords.dtype)[:, None]
    labels = np.zeros(coords.shape[0], np.int64)
    for _ in range(iters):
        c2 = (centers * centers).sum(1, dtype=centers.dtype)[None, :]
        cross = (coords[:, 0:1] * centers[None, :, 0]
                 + coords[:, 1:2] * centers[None, :, 1])
        d = np.maximum(x2 - f(2.0) * cross + c2, f(0.0)).astype(np.float64)
        d[:, ~active] = _BIG
        labels = d.argmin(1)
        counts = np.bincount(labels, minlength=kc)
        sums = np.zeros((kc, 2), coords.dtype)
        for c in range(2):
            sums[:, c] = f(np.bincount(labels, weights=coords[:, c],
                                       minlength=kc))
        centers = np.where(counts[:, None] > 0,
                           sums / f(np.maximum(counts, 1))[:, None], centers)
        centers = f(centers)
    return centers, labels


def _max_cluster_variance(coords, centers, labels, k, f):
    kc = centers.shape[0]
    sq = ((coords - centers[labels]) ** 2).sum(1, dtype=coords.dtype)
    per = f(np.bincount(labels, weights=sq, minlength=kc))
    counts = np.bincount(labels, minlength=kc)
    var = per / f(np.maximum(counts - 1, 1))
    live = (np.arange(kc) < k) & (counts > 0)
    return float(np.max(np.where(live, var, f(0.0))))


def digitize(lengths, incs, seed: int, *, tol: float, scl: float, k_min: int,
             k_max: int, n_max: int, lloyd_iters: int,
             dtype=np.float32) -> np.ndarray:
    """Online symbols of one session's pieces, in arrival order."""
    import jax

    f = lambda v: np.asarray(v, dtype)  # noqa: E731
    n_all = len(lengths)
    pieces = np.stack([f(lengths), f(incs)], axis=1)
    centers = np.zeros((k_max, 2), dtype)
    k = 0
    key = jax.random.key(int(seed) & 0xFFFFFFFF)
    bound = float(f(tol) * f(tol))
    symbols = np.zeros(n_all, np.int64)
    for i in range(n_all):
        n = i + 1
        p = pieces[:n]
        if n <= k_min:
            centers = np.zeros((k_max, 2), dtype)
            centers[:n] = p
            k = n
            symbols[i] = i
            continue
        cnt = f(n)
        mean = p.sum(0, dtype=dtype) / cnt
        std = np.sqrt(((p - mean) ** 2).sum(0, dtype=dtype) / cnt)
        std = np.where(std < f(1e-12), f(1.0), std)
        scales = np.stack([f(scl) / std[0], f(1.0) / std[1]]).astype(dtype)
        coords = f(p * scales)
        k_o = max(k, 1)
        k_hi = min(k_max, n)
        c, lab = _lloyd(coords, f(centers * scales), k_o, lloyd_iters, f)
        err = _max_cluster_variance(coords, c, lab, k_o, f)
        k = k_o
        while k < k_hi and err > bound:
            key, sub = jax.random.split(key)
            if k + 1 == k_o + 1:
                init = c.copy()
                init[k] = coords[n - 1]
            else:
                probs = np.zeros(n_max, np.float32)
                probs[:n] = np.float32(1.0) / np.float32(n)
                idx = np.asarray(jax.random.choice(
                    sub, n_max, shape=(k_max,), replace=False, p=probs))
                padded = np.zeros((n_max, 2), dtype)
                padded[:n] = coords
                init = padded[idx]
            k += 1
            c, lab = _lloyd(coords, init, k, lloyd_iters, f)
            err = _max_cluster_variance(coords, c, lab, k, f)
        counts = np.bincount(lab, minlength=k_max)
        sums = np.zeros((k_max, 2), dtype)
        for d in range(2):
            sums[:, d] = f(np.bincount(lab, weights=p[:, d], minlength=k_max))
        centers = f(sums / f(np.maximum(counts, 1))[:, None])
        symbols[i] = lab[n - 1]
    return symbols


def encode_session(endpoints, steps, t0: float, seed: int, cfg: dict,
                   dtype=np.float32) -> List[int]:
    """Symbols the receiver owes a session whose wire pieces are given."""
    f = lambda v: np.asarray(v, dtype)  # noqa: E731
    e = f(endpoints)
    prev_e = np.concatenate([f([t0]), e[:-1]])
    prev_s = np.concatenate([[1], np.asarray(steps)[:-1]])
    lengths = np.asarray(steps) - prev_s
    incs = f(e - prev_e)
    n = min(len(e), cfg["n_max"])
    return digitize(
        lengths[:n], incs[:n], seed, tol=cfg["tol"], scl=cfg["scl"],
        k_min=cfg["k_min"], k_max=cfg["k_max"], n_max=cfg["n_max"],
        lloyd_iters=cfg["lloyd_iters"], dtype=dtype)

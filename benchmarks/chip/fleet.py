"""The sensor data every cell draws from, fixed here so no program change
can move it.

A copy of the program's UCR-like synthetic families (``make_fleet``: smooth
spectra, quasi-periodic sensors, switching loads, random walks, pulse
trains, mixed in equal shares) and of the bring-up check's row filter: a
series is kept only if the sender's compressor cuts it into at least
``PIECE_MARGIN`` pieces fewer than the receiver's ``n_max`` buffer holds
(the noisiest families overflow it within 2048 points at tol 0.5; pieces
past ``n_max`` are dropped by design, which is not the path measured here).
"""
from __future__ import annotations

import zlib
from typing import List

import numpy as np

import reference

__all__ = ["FAMILIES", "make_fleet", "make_rows", "PIECE_MARGIN"]

PIECE_MARGIN = 16


def _grid(n, length):
    return np.linspace(0.0, 1.0, length)[None, :].repeat(n, 0)


def _sensor(rng, n, length):
    t = _grid(n, length)
    f = rng.uniform(3, 9, (n, 1))
    phase = rng.uniform(0, 2 * np.pi, (n, 1))
    amp2 = rng.uniform(0.1, 0.5, (n, 1))
    x = np.sin(2 * np.pi * f * t + phase) + amp2 * np.sin(4 * np.pi * f * t)
    return x + rng.normal(0, 0.08, x.shape)


def _device(rng, n, length):
    x = np.zeros((n, length))
    for i in range(n):
        pos = 0
        while pos < length:
            dur = int(rng.integers(length // 40 + 2, length // 8 + 4))
            level = rng.choice([0.0, 1.0, 2.0, 3.0]) + rng.normal(0, 0.05)
            x[i, pos: pos + dur] = level
            pos += dur
    return x + rng.normal(0, 0.05, x.shape)


def _motion(rng, n, length):
    x = np.cumsum(rng.normal(0, 1.0, (n, length)), axis=1)
    k = max(length // 100, 3)
    kernel = np.ones(k) / k
    sm = np.stack([np.convolve(r, kernel, mode="same") for r in x])
    return (sm - sm.mean(1, keepdims=True)) / (sm.std(1, keepdims=True) + 1e-9)


def _spectro(rng, n, length):
    t = _grid(n, length)
    c = rng.normal(0, 1, (n, 6))
    x = sum(c[:, k: k + 1] * t ** k for k in range(6))
    x = (x - x.mean(1, keepdims=True)) / (x.std(1, keepdims=True) + 1e-9)
    return x + rng.normal(0, 0.03, x.shape)


def _hemo(rng, n, length):
    t = _grid(n, length)
    rate = rng.uniform(8, 16, (n, 1))
    phase = (t * rate) % 1.0
    pulse = np.exp(-((phase - 0.2) ** 2) / 0.004) + 0.4 * np.exp(
        -((phase - 0.5) ** 2) / 0.01)
    drift = 0.3 * np.sin(2 * np.pi * t * rng.uniform(0.5, 1.5, (n, 1)))
    return pulse + drift + rng.normal(0, 0.04, pulse.shape)


FAMILIES = {"sensor": _sensor, "device": _device, "motion": _motion,
            "spectro": _spectro, "hemo": _hemo}


def _family(name: str, n: int, length: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed ^ zlib.crc32(name.encode()) & 0xFFFF)
    return FAMILIES[name](rng, n, length).astype(np.float32)


def make_fleet(n_streams: int, length: int, seed: int) -> np.ndarray:
    """Mixed-family slab ``(n_streams, length)``, families in equal shares."""
    rng = np.random.default_rng(seed)
    names = list(FAMILIES)
    per = [n_streams // len(names)] * len(names)
    per[0] += n_streams - sum(per)
    parts: List[np.ndarray] = []
    for name, k in zip(names, per):
        if k:
            parts.append(_family(name, k, length, int(rng.integers(1 << 30))))
    return np.concatenate(parts, axis=0)


def make_rows(n_rows: int, length: int, seed: int, cfg: dict):
    """``n_rows`` series of ``length`` points that fit ``n_max``, drawn in a
    seeded order from a slab of twice as many candidates, and the number of
    pieces the sender cuts each into."""
    cand = 2 * n_rows
    data = make_fleet(cand, length, seed)
    order = np.random.default_rng(seed).permutation(cand)
    comp = reference.compress(data, tol=cfg["tol"], len_max=cfg["len_max"],
                              alpha=cfg["alpha"], fused=True)
    pieces = comp["emit"].sum(1) + comp["tail_emit"]
    keep = [r for r in order if pieces[r] < cfg["n_max"] - PIECE_MARGIN]
    if len(keep) < n_rows:
        raise RuntimeError(f"only {len(keep)} of {cand} rows fit n_max")
    keep = np.asarray(keep[:n_rows])
    return data[keep], pieces[keep]

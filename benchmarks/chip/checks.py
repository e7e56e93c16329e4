"""The comparison that decides ``correct``.

A run's sample of finished sessions (drawn from the seed, the one with the
most pieces always in it) is run through the plain reference
(``reference.py``) from the raw points each session sent, and the served
delta stream (every DELTA frame then the CLOSED frame, concatenated) is
compared with it position by position:

* ``frame_mismatch``: share of positions whose symbol or piece endpoint
  differs (endpoints are raw points, so compared exactly), a missing or an
  extra position counting as a difference;
* ``sessions_failed``: sessions of the schedule that ended in ERROR or
  eviction, or never closed.

Each number is held to the limit in ``LIMITS``; PERF.md gives the readings
each limit was set from (sound runs over a dozen seeds and more, and the
lower-precision control).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import reference

__all__ = ["LIMITS", "compare", "reference_streams", "sample"]

# name -> limit; a run is correct when every number is at or below it
LIMITS = {"frame_mismatch": 0.12, "sessions_failed": 0}


def fused_compressor(cfg: dict, rehearse: bool) -> bool:
    """Whether the device that compresses a session rounds the compressor's
    bridge error through fused multiply-adds: the sensors' CPU does (as XLA
    compiles for a CPU), the TPU does not; the CPU rehearsal compresses on
    the CPU in both modes."""
    return cfg["compressor"] == "sensor" or rehearse


def sample(sessions: List[dict], n: int, seed: int) -> List[dict]:
    """Up to ``n`` finished sessions drawn from ``seed``, the longest kept."""
    done = [s for s in sessions if s["closed"] and not s["error"]
            and not s["evicted"]]
    if not done:
        return []
    longest = max(done, key=lambda s: s["n_pieces"])
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.permutation(len(rest))[: max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_streams(cfg: dict, rows: np.ndarray, picked: List[dict],
                      *, fused: bool, dtype=np.float32,
                      digitize_dtype=None) -> List[Dict[str, np.ndarray]]:
    """(symbols, endpoints) the reference owes each picked session.

    ``dtype`` rounds the compressor, ``digitize_dtype`` (default: the same)
    the digitizer: the pieces-mode control keeps the sensor's float32
    pieces and digitizes in the lower precision, as the chip would.
    """
    digitize_dtype = digitize_dtype or dtype
    out: List[dict] = [{} for _ in picked]
    by_len: Dict[int, List[int]] = {}
    for i, s in enumerate(picked):
        by_len.setdefault(s["points_sent"], []).append(i)
    for t_len, idx in by_len.items():
        x = np.stack([rows[picked[i]["row"]][:t_len] for i in idx])
        comp = reference.compress(x, tol=cfg["tol"], len_max=cfg["len_max"],
                                  alpha=cfg["alpha"], fused=fused,
                                  dtype=dtype)
        for j, i in enumerate(idx):
            e, steps = reference.wire_pieces(comp, j, t_len)
            syms = reference.encode_session(e, steps, float(x[j, 0]),
                                            picked[i]["seed"], cfg,
                                            dtype=digitize_dtype)
            out[i] = {"symbols": np.asarray(syms) % 256,
                      "endpoints": np.asarray(e, np.float32)[: len(syms)]}
    return out


def _mismatch(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]):
    """(positions that differ, positions) of one session's delta stream.

    A position differs when its symbol or its endpoint does; a missing or
    an extra position differs too."""
    gs, ge = np.asarray(got["symbols"]), np.asarray(got["endpoints"],
                                                    np.float32)
    ws, we = want["symbols"], want["endpoints"]
    n = min(len(gs), len(ws))
    bad = (gs[:n] != ws[:n]) | (ge[:n] != we[:n])
    return int(bad.sum()) + abs(len(gs) - len(ws)), max(len(gs), len(ws))


def compare(served: List[Dict[str, np.ndarray]],
            want: List[Dict[str, np.ndarray]], failed: int) -> dict:
    """The numbers compared, each with its limit."""
    bad = total = 0
    for got, ref in zip(served, want):
        b, n = _mismatch(got, ref)
        bad += b
        total += n
    numbers = {"frame_mismatch": bad / max(total, 1),
               "sessions_failed": failed}
    ok = bool(total) and all(numbers[k] <= LIMITS[k] for k in LIMITS)
    return {"correct": ok, "symbols_compared": total,
            "numbers": {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in numbers.items()}}

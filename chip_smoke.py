#!/usr/bin/env python3
"""Bring-up check of the SymED edge service on a TPU.

One process drives the served path through the entry points a user calls:
a ``TransportServer`` in front of a ``StreamServer`` at the paper's widths
(``PAPER_SYMED``: n_max 512, k_max 100, len_max 512, alpha 0.01, tol 0.5)
with 1024 resident slots, fed over loopback TCP by ``SenderClient``s: 64
sessions in pieces mode, whose senders compress on the host CPU as an IoT
node would, and 64 in raw mode, each 2048 points of ``make_fleet`` (the
session count is cut to fit the run time; see ``SESSIONS``).
Both served kernels run compiled: the Pallas Lloyd kernel in the donated
table step (``use_kernel=True``, the TPU default) and the Pallas DTW kernel
in the online monitor (``dtw_every=8``).

Phases, each of which must pass:

1. device    -- JAX reports a TPU; a silent fallback to the CPU fails here.
2. kernels   -- the compiled Pallas kernels against float64 and jnp oracles.
3. served    -- the loopback run above: every session closes, none fills
                its n_max piece buffer, the compiled table steps hold the
                kernel (``tpu_custom_call``).
4. agreement -- a fixed sample of sessions against ``symed_encode`` on the
                chip and on the host CPU, with the kernel on and off.

``--chips 4`` runs only the sharded slot table and what it is compared
with: one trace through a ``StreamServer`` sharded over a 4-chip ``data``
mesh and through an unsharded one-chip server in the same process.  Their
delta streams must be identical (the layout-invariance contract).

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failure exits non-zero without printing it.  Earlier lines carry the
bring-up record: compile and wall seconds, symbols, compression rate and
ms/symbol (a record of this run, not a benchmark metric).

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import threading
import time
from typing import Dict, List, Tuple

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

SLOTS = 1024           # resident slot-table capacity
WINDOW = 256           # sender window and table window cap (points)
# Cut to fit a few minutes: at 1024 slots one table step takes seconds on
# a v5e (the digitize loop runs to the widest span and the slowest k-growth
# in the table), so a full table of 1024 sessions of 2048-4096 points does
# not fit.  The table keeps its 1024 slots and the paper's widths; the
# trace keeps 128 of its sessions, each 2048 points.
LENGTH = 2048          # points each session ships
SESSIONS = 128         # half pieces mode, half raw mode
CONNS = 8              # sender sockets, each interleaving its sessions
DTW_EVERY = 8          # online DTW monitor cadence (windows)
SAMPLE = 16            # agreement sample per mode
# --chips 4: the sharded table against the one-chip table, both this size
SHARDED_SLOTS, SHARDED_SESSIONS, SHARDED_LENGTH = 256, 16, 1024
# A session whose stream compresses to n_max pieces or more overflows the
# receiver's buffer (pieces past it are dropped).  make_fleet's noisiest
# families do that within 2048 points at tol 0.5, so the trace keeps only
# rows that stay this many pieces below n_max on the host compressor; the
# margin covers a chip compressor that cuts a piece or two differently.
PIECE_MARGIN = 16
TIMEOUT = 600.0        # seconds any sender or serve loop may take
PROGRESS_EVERY = 30.0  # seconds between progress lines on stderr

# Agreement tiers, weakest first: "prefix" -- every symbol equal on the
# pieces both compressors cut alike (``compare``); "endpoints" -- every
# piece endpoint bitwise equal; "bitwise" -- endpoints and symbols.
TIERS = ("none", "prefix", "endpoints", "bitwise")
# Agreement with ``symed_encode`` the chip must show, with the kernel on
# and off: the strongest tier that held on a v5e, with its reason.  A
# pieces-mode session is compressed by its sender on the host CPU, a
# raw-mode one by the receiver on the chip.
_SAME = ("bitwise", "the reference ran on the device that compressed the "
         "session; the digitizer matches it with the kernel on and off")
_CROSS = ("prefix", "the chip's compressor cuts some pieces differently "
          "from the CPU's (float arithmetic), and a session diverges from "
          "the first such cut; up to it every symbol agrees")


def expected(ref_device: str, mode: str) -> Tuple[str, str]:
    """(tier, reason) pinned for a reference on ``ref_device``."""
    return _SAME if (ref_device == "tpu") == (mode == "raw") else _CROSS


@dataclasses.dataclass(frozen=True)
class Session:
    sid: str
    mode: str     # "pieces" | "raw"
    row: int      # row of the fleet slab
    length: int   # points the sender ships


def host_cpu():
    import jax

    return jax.devices("cpu")[0]


def make_sessions(n: int, length: int, cfg, seed: int):
    """``n`` sessions, alternating pieces/raw mode, over ``make_fleet`` rows.

    Rows of ``length`` points are drawn in a seeded order from a slab of
    ``2 n`` candidates; a row is kept if the host compressor cuts it into
    fewer than ``n_max - PIECE_MARGIN`` pieces.  Returns ``(sessions, data,
    rows skipped)``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.compress import compress_stream
    from repro.data.synthetic import make_fleet

    rng = np.random.default_rng(seed)
    cand = 2 * n
    data = np.asarray(make_fleet(cand, length, seed=seed), np.float32)
    order = rng.permutation(cand)
    with jax.default_device(host_cpu()):
        out = compress_stream(jnp.asarray(data), tol=cfg.tol,
                              len_max=cfg.len_max, alpha=cfg.alpha)
    pieces = np.asarray(out["n_pieces"])
    keep, skipped = [], 0
    for r in order:
        if len(keep) == n:
            break
        if pieces[r] < cfg.n_max - PIECE_MARGIN:
            keep.append(r)
        else:
            skipped += 1
    if len(keep) < n:
        raise RuntimeError(f"only {len(keep)} of {cand} rows fit n_max")
    sessions = [
        Session(sid=f"{'p' if i % 2 == 0 else 'r'}{i:04d}",
                mode="pieces" if i % 2 == 0 else "raw", row=int(r),
                length=length)
        for i, r in enumerate(keep)
    ]
    return sessions, data, skipped


def serve_over_loopback(server, sessions: List[Session], data, *, cfg,
                        window: int, conns: int, seed: int):
    """Serve ``sessions`` through a loopback ``TransportServer``.

    ``conns`` sender threads each open one ``SenderClient`` socket and
    interleave their sessions window by window.  The fleet samples in step,
    as sensors on one clock do: every sender ships window ``r`` of each of
    its sessions, then waits for the others before window ``r + 1``, so
    the receiver batches a round into few table steps.  The senders' JAX
    work runs on the host CPU.  Progress goes to stderr every
    ``PROGRESS_EVERY`` seconds.  Returns ``(results by sid, wall
    seconds)``; a failure in the serve loop or any sender raises here.
    """
    import jax

    from repro.launch.transport import (
        SenderClient, ServeThread, TransportServer, session_seed)

    transport = TransportServer(server, port=0)
    serving = ServeThread(transport, expect_sessions=len(sessions))
    results: Dict[str, dict] = {}
    errors: List[BaseException] = []
    cpu = host_cpu()
    groups = [sessions[i::conns] for i in range(min(conns, len(sessions)))]
    rounds = -(-max(s.length for s in sessions) // window)
    in_step = threading.Barrier(len(groups))

    def send(group: List[Session]) -> None:
        try:
            with jax.default_device(cpu):
                client = SenderClient("127.0.0.1", transport.port, cfg,
                                      reply_timeout=TIMEOUT)
                for s in group:
                    client.open(s.sid, session_seed(s.sid, seed), mode=s.mode)
                for r in range(rounds):
                    in_step.wait(timeout=TIMEOUT)
                    lo = r * window
                    for s in group:
                        if lo < s.length:
                            client.send(s.sid, data[s.row, lo:min(
                                lo + window, s.length)])
                for s in group:
                    res = client.close(s.sid)
                    labels, endpoints = client.delta_concat(s.sid)
                    results[s.sid] = {**res, "labels": labels,
                                      "endpoints": endpoints}
                client.shutdown()
        except BaseException as e:  # re-raised by the caller
            in_step.abort()
            errors.append(e)

    threads = [threading.Thread(target=send, args=(g,), daemon=True)
               for g in groups]
    done = threading.Event()
    t0 = time.perf_counter()

    def report_progress() -> None:
        while not done.wait(PROGRESS_EVERY):
            t = server.totals
            print(f"  progress {time.perf_counter() - t0:.0f}s: "
                  f"steps={t['steps']} points={t['points_in']} "
                  f"symbols={t['symbols_out']} closed={t['closed']}"
                  f"/{len(sessions)}", file=sys.stderr, flush=True)

    threading.Thread(target=report_progress, daemon=True).start()
    for t in threads:
        t.start()
    deadline = t0 + TIMEOUT
    for t in threads:
        t.join(timeout=max(deadline - time.perf_counter(), 0.0))
    wall = time.perf_counter() - t0
    done.set()
    with serving.root_cause():
        first = [e for e in errors
                 if not isinstance(e, threading.BrokenBarrierError)]
        if errors:
            raise (first or errors)[0]
        if any(t.is_alive() for t in threads):
            raise TimeoutError(f"senders still running after {TIMEOUT}s")
    serving.join(timeout=60.0)
    return results, wall


def check_served(results, sessions, cfg) -> Tuple[int, int]:
    """Every session closed cleanly below n_max; returns (symbols, points)."""
    assert len(results) == len(sessions), (len(results), len(sessions))
    symbols = points = 0
    for s in sessions:
        r = results[s.sid]
        assert not r["evicted"], f"{s.sid} was evicted"
        assert r["t_seen"] == s.length, (s.sid, r["t_seen"], s.length)
        n = int(r["n_pieces"])
        assert 0 < n < cfg.n_max, f"{s.sid}: {n} pieces (n_max {cfg.n_max})"
        assert len(r["labels"]) == n == len(r["endpoints"]), s.sid
        symbols += n
        points += s.length
    return symbols, points


def span_totals(server) -> str:
    """The server's recorded host spans, summed per name.  A harvest span
    waits for its step's device results, so it holds the device time."""
    totals: Dict[str, List[float]] = {}
    for name, ph, _, dur_ns, _ in server.obs.tracer.events():
        if ph == "X":
            count_s = totals.setdefault(name, [0, 0.0])
            count_s[0] += 1
            count_s[1] += dur_ns / 1e9
    return " ".join(f"{name}={n}x{s:.3f}"
                    for name, (n, s) in sorted(totals.items()))


def step_holds_kernel(server) -> bool:
    """Whether the compiled raw and pieces table steps call a Mosaic kernel."""
    import jax.numpy as jnp

    from repro.launch import stream

    cap, w = server.capacity, server.window_cap
    f = server._put(jnp.zeros((cap, w), jnp.float32))
    i = server._put(jnp.zeros((cap, w), jnp.int32))
    c = server._put(jnp.zeros((cap,), jnp.int32))
    s = server._put(jnp.zeros((cap,), jnp.float32))
    kw = dict(cfg=server.cfg, digitize_every_k=server.digitize_every_k,
              use_kernel=server.use_kernel, mesh=server._mesh)
    texts = [
        stream._table_step.lower(server._table, f, c, **kw).compile().as_text(),
        stream._table_step_pieces.lower(
            server._table, f, i, c, s, c, **kw).compile().as_text(),
    ]
    return all("tpu_custom_call" in t for t in texts)


# ------------------------------------------------------------------ phases


def phase_kernels(cfg, seed: int) -> None:
    """Compiled Pallas kernels against oracles, at the served widths.

    The Lloyd kernel is held to float64 numpy: each label must be a nearest
    active center (ties within float error allowed), and the per-cluster
    sums and counts must be those of the labels it returned.  The DTW
    kernel is held to the jnp oracle on the same chip.
    """
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    s, n, k = 16, cfg.n_max, cfg.k_max
    x = rng.normal(size=(s, n, 2)).astype(np.float32)
    mask = np.arange(n)[None, :] < rng.integers(1, n + 1, (s, 1))
    c = rng.normal(size=(s, k, 2)).astype(np.float32)
    act = np.arange(k)[None, :] < rng.integers(1, k + 1, (s, 1))
    lk, sk, ck = (np.asarray(a) for a in ops.kmeans_assign(
        jnp.asarray(x), jnp.asarray(mask), jnp.asarray(c), jnp.asarray(act)))
    x64, c64 = x.astype(np.float64), c.astype(np.float64)
    d = ((x64[:, :, None, :] - c64[:, None, :, :]) ** 2).sum(-1)
    d = np.where(act[:, None, :], d, np.inf)
    got = np.take_along_axis(d, lk[..., None], -1)[..., 0]
    gap = np.where(mask, got - d.min(-1), 0.0)
    onehot = (lk[..., None] == np.arange(k)) & mask[..., None]
    want_sums = np.einsum("snk,snd->skd", onehot.astype(np.float64), x64)
    assert np.all(lk[~mask] == 0), "masked pieces must carry label 0"
    assert gap.max() <= 1e-4 * (1.0 + d.min()), gap.max()
    np.testing.assert_allclose(sk, want_sums, rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(ck, onehot.sum(1))

    xs = jnp.asarray(rng.normal(size=(8, 2048)).cumsum(1), jnp.float32)
    ys = xs + jnp.asarray(rng.normal(0, 0.3, (8, 2048)), jnp.float32)
    errs = []
    for band in (None, 32):
        dk = np.asarray(ops.dtw(xs, ys, band=band))
        dr = np.asarray(ref.dtw_batch_ref(xs, ys, band=band))
        np.testing.assert_allclose(dk, dr, rtol=1e-4)
        errs.append(float(np.max(np.abs(dk - dr) / dr)))
    print(f"kernels: kmeans S={s} N={n} K={k} max_argmin_gap={gap.max()} "
          f"max_sum_err={float(np.max(np.abs(sk - want_sums)))}; "
          f"dtw N=2048 max_rel_err={max(errs)}", flush=True)


def build_server(cfg, *, slots: int, window: int, dtw_every: int, seed: int,
                 use_kernel=None, mesh=None):
    """A pretraced ``StreamServer``; returns ``(server, compile seconds)``."""
    from repro.launch.stream import StreamServer

    t0 = time.perf_counter()
    server = StreamServer(
        cfg, max_sessions=slots, window_cap=window, digitize_every_k=1,
        dtw_every=dtw_every, use_kernel=use_kernel, pretrace=True,
        seed=seed, mesh=mesh)
    return server, time.perf_counter() - t0


def phase_served(cfg, sessions, data, *, slots, window, conns, seed):
    from repro.kernels.dtw import dtw_pallas

    server, compile_s = build_server(cfg, slots=slots, window=window,
                                     dtw_every=DTW_EVERY, seed=seed)
    assert server.use_kernel, "the TPU default must be the Pallas kernel"
    print(f"served compile_seconds={compile_s:.3f} (pretrace: raw and "
          f"pieces table steps at {slots} slots, use_kernel=True)",
          flush=True)
    results, wall = serve_over_loopback(
        server, sessions, data, cfg=cfg, window=window, conns=conns,
        seed=seed)
    symbols, points = check_served(results, sessions, cfg)
    n_pieces = [int(results[s.sid]["n_pieces"]) for s in sessions]
    print(f"served sessions={len(sessions)} slots={slots} window={window} "
          f"wall_seconds={wall:.3f} points={points} symbols={symbols} "
          f"compression_rate={symbols / points:.5f} "
          f"ms_per_symbol={1e3 * wall / symbols:.4f} "
          f"max_pieces_per_session={max(n_pieces)} "
          f"table_steps={server.totals['steps']}", flush=True)
    print(f"served host spans (count x seconds): {span_totals(server)}",
          flush=True)
    assert server.totals["closed"] == len(sessions), server.totals
    t0 = time.perf_counter()
    assert step_holds_kernel(server), "no Mosaic kernel in the table step"
    assert dtw_pallas._cache_size() > 0, "the DTW monitor never ran"
    print(f"served kernels: both table steps hold tpu_custom_call, DTW "
          f"monitor kernel compiled ({time.perf_counter() - t0:.3f}s to "
          f"check)", flush=True)
    return results


def reference(cfg, ts, key, device):
    """``symed_encode`` on ``device``: (symbols, wire endpoints)."""
    import jax
    import jax.numpy as jnp

    from repro.core.compress import compress_stream
    from repro.core.symed import symed_encode

    with jax.default_device(device):
        ts_d = jnp.asarray(ts)
        out = symed_encode(ts_d, cfg, key, reconstruct=False)
        n = int(out["n_pieces"])
        ev = compress_stream(ts_d, tol=cfg.tol, len_max=cfg.len_max,
                             alpha=cfg.alpha)
        eps = list(np.asarray(ev["endpoint"])[np.asarray(ev["emit"])])
        if bool(ev["tail"].emit):
            eps.append(float(ev["tail"].endpoint))
    return (np.asarray(out["symbols_online"])[:n].astype(np.int32),
            np.asarray(eps, np.float32))


def compare(results, refs) -> dict:
    """Agreement of served delta streams with reference (labels, endpoints).

    A session's matched prefix is its pieces up to the first endpoint that
    differs beyond float rounding (the two compressors cut differently from
    there on).  ``symbol_agreement`` counts equal symbols over the matched
    prefixes and ``matched_pieces`` their share of the reference's pieces,
    sessions pooled; ``max_count_rel_diff`` is the largest relative
    difference in piece count.
    """
    same_n = eps_bit = lab_bit = agree = matched = total = 0
    max_count = 0.0
    for sid, (want_l, want_e) in refs.items():
        got_l = np.asarray(results[sid]["labels"], np.int32)
        got_e = np.asarray(results[sid]["endpoints"], np.float32)
        n = min(len(got_e), len(want_e))
        close = np.isclose(got_e[:n], want_e[:n], rtol=1e-5, atol=1e-5)
        p = n if close.all() else int(np.argmin(close))
        max_count = max(max_count,
                        abs(len(got_l) - len(want_l)) / max(len(want_l), 1))
        if len(got_l) == len(want_l) == len(got_e) == len(want_e):
            same_n += 1
            eps_bit += int(np.array_equal(got_e, want_e))
            lab_bit += int(np.array_equal(got_l, want_l))
        agree += int(np.sum(got_l[:p] == want_l[:p]))
        matched += p
        total += len(want_l)
    m = len(refs)
    stats = {"sessions": m, "same_count": same_n, "endpoints_bitwise": eps_bit,
             "labels_bitwise": lab_bit,
             "matched_pieces": matched / max(total, 1),
             "symbol_agreement": agree / max(matched, 1),
             "max_count_rel_diff": max_count}
    if lab_bit == eps_bit == m:
        stats["tier"] = "bitwise"
    elif eps_bit == m:
        stats["tier"] = "endpoints"
    elif agree == matched:
        stats["tier"] = "prefix"
    else:
        stats["tier"] = "none"
    return stats


def phase_agreement(cfg, sample, data, kernel_results, *, window, seed):
    """The sample against ``symed_encode`` on the chip and the host CPU."""
    import jax

    from repro.launch.transport import session_seed

    server, compile_s = build_server(cfg, slots=len(sample), window=window,
                                     dtw_every=0, seed=seed, use_kernel=False)
    ref_results, wall = serve_over_loopback(
        server, sample, data, cfg=cfg, window=window, conns=4, seed=seed)
    check_served(ref_results, sample, cfg)
    print(f"agreement: use_kernel=False server on {len(sample)} slots "
          f"(compile {compile_s:.3f}s, serve {wall:.3f}s)", flush=True)
    devices = {"tpu": jax.devices()[0], "cpu": host_cpu()}
    refs = {name: {} for name in devices}
    t0 = time.perf_counter()
    for s in sample:
        ts = data[s.row, :s.length]
        key = jax.random.key(session_seed(s.sid, seed))
        for name, dev in devices.items():
            refs[name][s.sid] = reference(cfg, ts, key, dev)
    print(f"agreement: references for {len(sample)} sessions on "
          f"{sorted(devices)} in {time.perf_counter() - t0:.3f}s", flush=True)
    failed = []
    for use_kernel, results in ((True, kernel_results),
                                (False, ref_results)):
        for name in devices:
            for mode in ("pieces", "raw"):
                part = {s.sid: refs[name][s.sid] for s in sample
                        if s.mode == mode}
                stats = compare(results, part)
                want, why = expected(name, mode)
                print(f"agreement use_kernel={use_kernel} ref={name} "
                      f"mode={mode}: "
                      + " ".join(f"{k}={v}" for k, v in stats.items())
                      + f" expected={want} ({why})", flush=True)
                if TIERS.index(stats["tier"]) < TIERS.index(want):
                    failed.append((use_kernel, name, mode, stats["tier"],
                                   want))
    assert not failed, f"agreement below what was pinned: {failed}"


def phase_sharded(cfg, *, seed: int, n_chips: int) -> None:
    """One trace through a mesh-sharded table and an unsharded one."""
    from repro.launch.fleet import fleet_data_mesh

    sessions, data, _ = make_sessions(SHARDED_SESSIONS, SHARDED_LENGTH, cfg,
                                      seed)
    mesh = fleet_data_mesh(n_chips)
    outs = {}
    for name, m in (("one-chip", None), (f"{n_chips}-chip", mesh)):
        server, compile_s = build_server(cfg, slots=SHARDED_SLOTS,
                                         window=WINDOW, dtw_every=0,
                                         seed=seed, mesh=m)
        results, wall = serve_over_loopback(
            server, sessions, data, cfg=cfg, window=WINDOW, conns=4,
            seed=seed)
        symbols, points = check_served(results, sessions, cfg)
        assert step_holds_kernel(server), f"{name}: no kernel in the step"
        print(f"sharded {name}: slots={SHARDED_SLOTS} "
              f"sessions={len(sessions)} "
              f"compile_seconds={compile_s:.3f} wall_seconds={wall:.3f} "
              f"symbols={symbols} points={points}", flush=True)
        outs[name] = results
    a, b = outs.values()
    same = sum(
        int(np.array_equal(a[s.sid]["labels"], b[s.sid]["labels"])
            and np.array_equal(a[s.sid]["endpoints"], b[s.sid]["endpoints"]))
        for s in sessions)
    print(f"sharded: identical delta streams for {same}/{len(sessions)} "
          f"sessions", flush=True)
    assert same == len(sessions), "sharded and one-chip delta streams differ"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded slot table on a 4-chip "
                         "mesh against the one-chip table")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the data and the digitizer keys")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    from repro.configs.symed_paper import PAPER_SYMED as cfg

    devices = jax.devices()
    platform, kind, count = (devices[0].platform, devices[0].device_kind,
                             len(devices))
    print(f"device platform={platform} kind={kind} count={count} "
          f"compile_cache={cache}", flush=True)
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX has {count}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    if args.chips > 1:
        phase_sharded(cfg, seed=args.seed, n_chips=args.chips)
    else:
        phase_kernels(cfg, args.seed)
        sessions, data, skipped = make_sessions(SESSIONS, LENGTH, cfg,
                                                args.seed)
        print(f"trace: {len(sessions)} sessions of {LENGTH} points "
              f"(cut from 1024 sessions of 2048-4096 points to fit the run "
              f"time; slots and widths not cut); {skipped} drawn rows "
              f"skipped for compressing to >= n_max - {PIECE_MARGIN} pieces",
              flush=True)
        results = phase_served(cfg, sessions, data, slots=SLOTS,
                               window=WINDOW, conns=CONNS, seed=args.seed)
        sample = ([s for s in sessions if s.mode == "pieces"][:SAMPLE]
                  + [s for s in sessions if s.mode == "raw"][:SAMPLE])
        phase_agreement(cfg, sample, data, results, window=WINDOW,
                        seed=args.seed)
    print(f"total_seconds={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

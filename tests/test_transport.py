"""Wire-transport battery: framing edge cases + loopback socket equivalence.

Two layers:

* ``TestFraming`` -- the codec alone.  TCP delivers byte *streams*, so the
  property battery re-slices a multi-frame byte string at random boundaries
  and requires the ``FrameDecoder`` to reassemble the identical frame
  sequence (partial length prefixes, frames split mid-payload, many frames
  per read).
* ``TestLoopback`` -- a real ``TransportServer`` on 127.0.0.1 with
  ``SenderClient``s in the test process.  The service contract carries over
  the socket: for raw-in and compressed-in senders alike, the concatenated
  DELTA frames plus the CLOSED closing frame are bitwise-equal to one-shot
  ``symed_encode`` -- including runs where the slot table autoscaled, and
  with sessions interleaving DATA over one connection.
"""
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from conftest import make_stream

from repro.core.compress import compress_stream
from repro.core.receiver import (
    delta_frame_bytes, pack_delta_frame, pack_piece_tuples,
    unpack_delta_frame, unpack_piece_tuples,
)
from repro.core.symed import SymEDConfig, symed_encode
from repro.launch.stream import StreamServer
from repro.launch.transport import (
    CLOSE, DATA, DELTA, ERROR, OPEN, FrameDecoder, SenderClient,
    ServeThread, TransportServer, decode_close, decode_data_pieces, decode_data_raw,
    encode_close, encode_data_pieces, encode_data_raw, encode_delta,
    encode_error, encode_open, session_seed,
)

CFG = SymEDConfig(tol=0.5, alpha=0.02, scl=1.0, k_min=3, k_max=8,
                  len_max=32, n_max=64, lloyd_iters=5)


# ------------------------------------------------------------------ framing


class TestFraming:
    def test_frame_roundtrip_each_type(self):
        dec = FrameDecoder()
        w = np.linspace(-1, 1, 7, dtype=np.float32)
        eps = np.asarray([0.5, -2.0], np.float32)
        steps = np.asarray([3, 9], np.int32)
        wire = (encode_open("sess-a", 1, 0xDEADBEEF)
                + encode_data_raw("sess-a", w)
                + encode_data_pieces("sess-a", 1.5, 17, eps, steps)
                + encode_close("sess-a", 17, -2.5)
                + encode_delta("sess-a", [1, 2], [0.1, 0.2])
                + encode_error("sess-a", "nope"))
        frames = dec.feed(wire)
        assert [f.type for f in frames] == [OPEN, DATA, DATA, CLOSE, DELTA,
                                            ERROR]
        assert all(f.sid == "sess-a" for f in frames)
        np.testing.assert_array_equal(decode_data_raw(frames[1].payload), w)
        t0, t_seen, e, s = decode_data_pieces(frames[2].payload)
        assert (t0, t_seen) == (1.5, 17)
        np.testing.assert_array_equal(e, eps)
        np.testing.assert_array_equal(s, steps)
        assert decode_close(frames[3].payload) == (17, -2.5)
        labels, endpoints = unpack_delta_frame(frames[4].payload)
        np.testing.assert_array_equal(labels, [1, 2])
        np.testing.assert_array_equal(endpoints,
                                      np.asarray([0.1, 0.2], np.float32))

    @given(st.integers(0, 31))
    @settings(max_examples=16, deadline=None)
    def test_partial_frames_across_recv_boundaries(self, seed):
        """Any re-slicing of the byte stream decodes to the same frames --
        split mid-length-prefix, mid-sid, mid-payload, or many per read."""
        rng = np.random.default_rng(7100 + seed)
        frames_in = []
        wire = b""
        for i in range(int(rng.integers(2, 8))):
            sid = f"s{int(rng.integers(0, 4))}"
            kind = int(rng.integers(0, 3))
            if kind == 0:
                wire += encode_open(sid, i % 2, i)
                frames_in.append((OPEN, sid))
            elif kind == 1:
                w = rng.normal(size=int(rng.integers(1, 40))).astype(np.float32)
                wire += encode_data_raw(sid, w)
                frames_in.append((DATA, sid))
            else:
                wire += encode_close(sid, int(rng.integers(0, 100)))
                frames_in.append((CLOSE, sid))
        dec = FrameDecoder()
        out = []
        pos = 0
        while pos < len(wire):
            n = int(rng.integers(1, 11))
            out.extend(dec.feed(wire[pos: pos + n]))
            pos += n
        assert [(f.type, f.sid) for f in out] == frames_in
        assert not dec.feed(b"")  # nothing buffered mid-frame

    def test_bad_length_prefix_rejected(self):
        dec = FrameDecoder()
        with pytest.raises(ValueError, match="bad frame length"):
            dec.feed(b"\xff\xff\xff\xff rest")
        with pytest.raises(ValueError, match="bad frame length"):
            FrameDecoder().feed(b"\x00\x00\x00\x01x")

    def test_delta_frame_bytes_matches_packed_length(self):
        """The accounted DELTA bytes are the *actual* wire bytes."""
        for n in (0, 1, 7):
            buf = pack_delta_frame(np.arange(n), np.arange(n, dtype=np.float32))
            assert len(buf) == float(delta_frame_bytes(n))

    def test_piece_tuples_roundtrip(self):
        eps = np.asarray([1.25, -3.5, 0.0], np.float32)
        steps = np.asarray([5, 111, 65000], np.int32)
        e, s = unpack_piece_tuples(pack_piece_tuples(eps, steps), 3)
        np.testing.assert_array_equal(e, eps)
        np.testing.assert_array_equal(s, steps)


# ----------------------------------------------------------------- loopback


class _Loopback:
    """A served StreamServer on 127.0.0.1 with a deterministic shutdown."""

    def __init__(self, expect_sessions, **server_kw):
        kw = dict(max_sessions=4, window_cap=32, digitize_every_k=1)
        kw.update(server_kw)
        self.stream = StreamServer(CFG, **kw)
        self.transport = TransportServer(self.stream, port=0)
        self.serving = ServeThread(self.transport,
                                   expect_sessions=expect_sessions)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.serving.join(timeout=60)


def _feed_and_close(client, sids, streams, rng, lo=1, hi=49):
    """Deliver each stream in ragged interleaved arrivals, then close all."""
    cursors = {sid: 0 for sid in sids}
    while any(cursors[sid] < len(streams[sid]) for sid in sids):
        for sid in sids:
            if cursors[sid] >= len(streams[sid]):
                continue
            n = int(rng.integers(lo, hi))
            client.send(sid, streams[sid][cursors[sid]: cursors[sid] + n])
            cursors[sid] += n
    return {sid: client.close(sid) for sid in sids}


def _assert_matches_encode(client, sid, ts, seed, res):
    labels, endpoints = client.delta_concat(sid)
    key = jax.random.key(session_seed(sid, seed))
    ref = symed_encode(jnp.asarray(ts[: res["t_seen"]]), CFG, key,
                       reconstruct=False)
    n = int(ref["n_pieces"])
    assert res["n_pieces"] == n, sid
    np.testing.assert_array_equal(
        labels, np.asarray(ref["symbols_online"])[:n],
        err_msg=f"{sid}: delta labels over the wire")
    ev = compress_stream(jnp.asarray(ts[: res["t_seen"]]), tol=CFG.tol,
                         len_max=CFG.len_max, alpha=CFG.alpha)
    want_eps = list(np.asarray(ev["endpoint"])[np.asarray(ev["emit"])])
    if bool(ev["tail"].emit):
        want_eps.append(float(ev["tail"].endpoint))
    np.testing.assert_array_equal(
        endpoints, np.asarray(want_eps, np.float32),
        err_msg=f"{sid}: delta endpoints over the wire")


@pytest.mark.parametrize("mode", ["raw", "pieces"])
def test_loopback_bitwise(mode, rng):
    """Interleaved sessions over one socket, both transport modes: the
    returned delta stream is bitwise-equal to one-shot symed_encode."""
    seed = 5
    streams = {f"t-{mode}-{i}": make_stream(rng, 128) for i in range(3)}
    sids = list(streams)
    with _Loopback(expect_sessions=len(sids)) as lb:
        client = SenderClient("127.0.0.1", lb.transport.port, CFG, mode=mode)
        for sid in sids:
            client.open(sid, session_seed(sid, seed))
        results = _feed_and_close(client, sids, streams, rng)
        for sid in sids:
            assert results[sid]["t_seen"] == 128
            _assert_matches_encode(client, sid, streams[sid], seed,
                                   results[sid])
        client.shutdown()


def test_loopback_pieces_compresses_wire(rng):
    """Compressed-in mode puts measurably less than 4 B/point on the wire,
    and the server's wire_in accounting sees it."""
    streams = {f"c-{i}": make_stream(rng, 160) for i in range(2)}
    with _Loopback(expect_sessions=2) as lb:
        client = SenderClient("127.0.0.1", lb.transport.port, CFG,
                              mode="pieces")
        for sid in streams:
            client.open(sid, session_seed(sid, 0))
        results = _feed_and_close(client, list(streams), streams, rng,
                                  lo=20, hi=41)
        client.shutdown()
    points = sum(r["t_seen"] for r in results.values())
    assert client.payload_bytes < 4.0 * points, (
        client.payload_bytes, 4.0 * points)
    rep = lb.stream.report(1.0)
    assert 0 < rep["wire_in_ratio"] < 1.0, rep["wire_in_ratio"]
    # StreamServer books the logical hello (4 B at open) while the client
    # books the CLOSE header -- the two counts differ only by that per-
    # session scaffolding
    assert abs(rep["wire_in_bytes"] - client.payload_bytes) <= 2 * len(streams)
    summ = lb.transport.summary()
    assert summ["pieces_ratio"] < 1.0
    assert summ["payload_bytes_pieces"] == pytest.approx(client.payload_bytes)


def test_raw_and_pieces_modes_agree(rng):
    """The same stream + digitizer seed through either transport mode yields
    the identical symbol stream (the compressed-in scatter reproduces the
    raw-mode receiver state bitwise)."""
    ts = make_stream(rng, 128)
    out = {}
    for mode in ("raw", "pieces"):
        with _Loopback(expect_sessions=1) as lb:
            client = SenderClient("127.0.0.1", lb.transport.port, CFG,
                                  mode=mode)
            client.open("same", 1234)
            for c in range(0, 128, 24):
                client.send("same", ts[c: c + 24])
            res = client.close("same")
            out[mode] = (res["n_pieces"], *client.delta_concat("same"))
            client.shutdown()
    assert out["raw"][0] == out["pieces"][0]
    np.testing.assert_array_equal(out["raw"][1], out["pieces"][1])
    np.testing.assert_array_equal(out["raw"][2], out["pieces"][2])


def test_close_unknown_session_keeps_serving(rng):
    """A CLOSE for a session the receiver never saw earns an ERROR frame;
    the connection and the server survive it."""
    ts = make_stream(rng, 96)
    with _Loopback(expect_sessions=1) as lb:
        client = SenderClient("127.0.0.1", lb.transport.port, CFG, mode="raw")
        client.sock.sendall(encode_close("ghost"))
        with pytest.raises(RuntimeError, match="unknown session"):
            client._drain(block=True)
        # same connection, same decoder: a real session still round-trips
        client.open("real", session_seed("real", 0))
        client.send("real", ts)
        res = client.close("real")
        _assert_matches_encode(client, "real", ts, 0, res)
        client.shutdown()


def test_duplicate_open_rejected(rng):
    with _Loopback(expect_sessions=1) as lb:
        client = SenderClient("127.0.0.1", lb.transport.port, CFG, mode="raw")
        client.open("dup", 0)
        client.sock.sendall(encode_open("dup", 0, 0))
        with pytest.raises(RuntimeError, match="already open"):
            client._drain(block=True)
        client.send("dup", make_stream(rng, 96))
        client.close("dup")
        client.shutdown()


def test_eviction_over_transport(rng):
    """LRU eviction reaches the sender as an unsolicited CLOSED(evicted):
    close() returns the parked prefix result instead of erroring, the
    prefix delta stream verifies bitwise, and the client's other sessions
    are unaffected."""
    seed = 3
    streams = {f"e-{i}": make_stream(rng, 96) for i in range(3)}
    sids = list(streams)

    def wait_delta(client, sid):
        # sync point: the server has ingested this session's data (DATA is
        # staged within a tick; LRU order needs the ingest to have happened
        # before the eviction-triggering OPEN arrives)
        while not client._sessions[sid].deltas:
            client._drain(block=True)

    with _Loopback(expect_sessions=3, max_sessions=2,
                   evict_idle=True) as lb:
        client = SenderClient("127.0.0.1", lb.transport.port, CFG, mode="raw")
        client.open(sids[0], session_seed(sids[0], seed))
        client.open(sids[1], session_seed(sids[1], seed))
        client.send(sids[0], streams[sids[0]][:40])
        wait_delta(client, sids[0])
        client.send(sids[1], streams[sids[1]])
        wait_delta(client, sids[1])
        client.open(sids[2], session_seed(sids[2], seed))  # evicts e-0 (LRU)
        client.send(sids[2], streams[sids[2]])
        res0 = client.close(sids[0])   # already settled by the eviction
        assert res0["evicted"] and res0["t_seen"] == 40
        _assert_matches_encode(client, sids[0], streams[sids[0]], seed, res0)
        for sid in sids[1:]:
            res = client.close(sid)
            assert not res["evicted"]
            _assert_matches_encode(client, sid, streams[sid], seed, res)
        client.shutdown()
    assert lb.stream.totals["evicted"] == 1


def test_malformed_payload_drops_conn_not_server(rng):
    """Garbage inside a well-framed body must not kill the serve loop: the
    offending connection is dropped, other tenants keep streaming."""
    import struct as _struct

    from repro.launch.transport import OPEN as _OPEN

    ts = make_stream(rng, 96)
    with _Loopback(expect_sessions=1) as lb:
        bad = SenderClient("127.0.0.1", lb.transport.port, CFG, mode="raw")
        # OPEN frame with a truncated payload (sid present, body too short)
        sid_b = b"bad"
        body = _struct.pack("!BB", _OPEN, len(sid_b)) + sid_b + b"\x01"
        bad.sock.sendall(_struct.pack("!I", len(body)) + body)
        good = SenderClient("127.0.0.1", lb.transport.port, CFG, mode="raw")
        good.open("good", session_seed("good", 0))
        good.send("good", ts)
        res = good.close("good")
        _assert_matches_encode(good, "good", ts, 0, res)
        good.shutdown()
        bad.shutdown()


def test_loopback_autoscale_resizes_preserve_deltas(rng):
    """Sessions arriving over the wire force table grows (1 -> 4) and the
    drain-down forces shrinks; every session's delta stream stays bitwise."""
    seed = 9
    streams = {f"a-{i}": make_stream(rng, 96) for i in range(4)}
    sids = list(streams)
    # shrink_patience=1: with only 4 sessions the drain gives just two
    # low-occupancy closes, so the default patience would (correctly) hold
    # capacity at 4.  Patience semantics are covered by the hysteresis
    # battery in test_stream_service.py; here we want the resizes to fire
    # so the wire-level delta streams are exercised across them.
    with _Loopback(expect_sessions=4, max_sessions=4, autoscale=True,
                   min_slots=1, shrink_patience=1) as lb:
        client = SenderClient("127.0.0.1", lb.transport.port, CFG,
                              mode="pieces")
        for sid in sids:
            client.open(sid, session_seed(sid, seed))
        results = _feed_and_close(client, sids, streams, rng, lo=16, hi=33)
        for sid in sids:
            _assert_matches_encode(client, sid, streams[sid], seed,
                                   results[sid])
        client.shutdown()
    assert lb.stream.totals["grows"] >= 2, lb.stream.totals
    assert lb.stream.totals["shrinks"] >= 1, lb.stream.totals
    assert lb.stream.capacity == 1


# ------------------------------------------------------ serve-loop failures


def test_serve_loop_failure_reaches_the_sender(rng, monkeypatch):
    """A serve loop that dies mid-session is re-raised where the sender
    runs, not left as a connection error or a timeout."""
    def boom(self, *a, **kw):
        raise ValueError("boom in the device step")

    monkeypatch.setattr(StreamServer, "ingest_many", boom)
    stream = StreamServer(CFG, max_sessions=2, window_cap=32)
    transport = TransportServer(stream, port=0)
    serving = ServeThread(transport, expect_sessions=1)
    client = SenderClient("127.0.0.1", transport.port, CFG, mode="raw",
                          reply_timeout=30)
    with pytest.raises(RuntimeError, match="serve loop failed") as info:
        with serving.root_cause():
            client.open("s", session_seed("s", 0))
            client.send("s", make_stream(rng, 32))
            client.close("s")
    assert isinstance(info.value.__cause__, ValueError)
    with pytest.raises(RuntimeError, match="boom in the device step"):
        serving.join(timeout=5)
    client.shutdown()


def test_serve_loop_still_running_after_join_is_an_error():
    stop = threading.Event()
    transport = TransportServer(
        StreamServer(CFG, max_sessions=2, window_cap=32), port=0)
    serving = ServeThread(transport, stop=stop)
    with pytest.raises(TimeoutError, match="still running"):
        serving.join(timeout=0.2)
    stop.set()
    serving.join(timeout=10)


def test_sender_process_never_initialises_an_accelerator():
    """The sender imports its modules without touching any JAX backend,
    then pins the CPU: the receiver next to it keeps the chip."""
    code = (
        "from repro.launch import transport\n"
        "import repro.core.symed, repro.data.synthetic, repro.core.compress\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, dict(xla_bridge._backends)\n"
        "transport.pin_host_cpu()\n"
        "import jax.numpy as jnp\n"
        "jnp.ones(3).block_until_ready()\n"
        "assert list(xla_bridge._backends) == ['cpu'], "
        "list(xla_bridge._backends)\n"
    )
    repo = Path(__file__).resolve().parents[1]
    env = {**os.environ, "PYTHONPATH": str(repo / "src")}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr

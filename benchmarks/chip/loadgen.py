"""Open-loop sensor fleet: the load generator child of ``run.py``.

Runs as its own process with JAX pinned to the host CPU, so it never holds
the chip.  It speaks the program's wire format over loopback TCP, as the
fleet's sensors (or the gateways in front of them) would.

Phases, each announced on stdout for the parent:

1. set-up: draw the series from the seed, cut every frame the run will
   send (pieces mode runs the sensor's compressor here, on the CPU, as the
   sensor would), and build the whole send schedule;
2. warm-up: a few sessions sent closed-loop end to end, so every program
   the window's traffic runs has compiled;
3. fill (``FILL``, then ``WARM``): every sensor has been streaming before
   the window opens.  Each stands at a seeded window of its first series,
   and the windows before it are sent at once; the parent answers
   ``FILLED`` once the server has read and processed every byte sent, and
   the replies are read.  So the window sees sessions at every point of
   their series, and series closing and the next opening;
4. after the parent's ``GO``: the open-loop schedule (``WINDOW t0 t1`` on
   the shared monotonic clock).  Each sensor sends one window every
   period at its own seeded phase, whatever the server does: frames queue
   in user space when the socket is full, and the send stamp is the time
   the frame left that queue.  A series closes after its last window and
   the sensor's next series opens on its next window;
   The window opens ``RAMP_S`` after the schedule starts;
5. after the window no new series opens; open series finish on schedule,
   then every CLOSED frame is awaited, and the record is written (``DONE``).

A symbol's latency runs from the due time of the window that completed its
piece to the receipt of the frame that carries it.  A piece ends at the raw
point equal to its endpoint (the generator holds the series) and is cut
while the next point is ingested, so the window that shipped that point is
the first from which the server could have made the symbol; a series' last
piece is completed by its CLOSE.  The wait for the data itself (a piece
that spans several windows) is the sensor's, not the server's, and is not
counted.
"""
from __future__ import annotations

import json
import os
import pathlib
import selectors
import socket
import sys
import time
from typing import List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import fleet  # noqa: E402


WARM_CANDIDATES = 8
WARM_SESSIONS = 2        # closed-loop sessions that compile every program
CONNECTIONS = 16         # loopback connections the fleet shares
WARM_TIMEOUT_S = 900.0   # warm-up and fill, first run of a checkout included
DRAIN_TIMEOUT_S = 120.0  # after the window, for every CLOSED frame
# The schedule starts this long before the window opens.  The fill leaves
# the server idle; a step of the filled fleet takes seconds, so the queue
# needs a few steps to reach the level it keeps.
RAMP_S = 10.0


class Session:
    __slots__ = ("sid", "row", "seed", "dues", "labels",
                 "endpoints", "closed", "error", "evicted", "n_pieces",
                 "t_seen", "cursor", "sent", "filled", "received")

    def __init__(self, sid, row, seed):
        self.sid, self.row, self.seed = sid, row, seed
        self.dues: List[float] = []   # due time of each window, then CLOSE
        self.labels: List[np.ndarray] = []
        self.endpoints: List[np.ndarray] = []
        self.closed = False
        self.error = None
        self.evicted = False
        self.n_pieces = 0
        self.t_seen = 0
        self.cursor = 0               # next series index an endpoint may match
        self.sent = 0                 # windows sent
        self.filled = 0               # windows sent before the window opens
        self.received = 0             # symbols received

    def windows(self, seconds: float) -> int:
        """Windows this session sends in a run whose window lasts
        ``seconds``: those of the fill and those due before it closes."""
        return sum(1 for d in self.dues[:-1] if d < RAMP_S + seconds)


class Conn:
    def __init__(self, sock):
        self.sock = sock
        self.out = bytearray()
        self.sent = 0          # bytes handed to the kernel so far
        self.queued = 0        # bytes ever queued
        self.marks: List[tuple] = []  # (end offset, due) of queued frames
        self.mark_i = 0


def _frames(spec, rows, sessions, comp):
    """All bytes of every series: (open, [data per window], [close after
    each window])."""
    from repro.launch.transport import (
        MODE_PIECES, MODE_RAW, encode_close, encode_data_pieces,
        encode_data_raw, encode_open)

    w = spec["window_points"]
    n_win = spec["series_points"] // w
    pieces = spec["mode"] == "pieces"
    out = []
    for s in sessions:
        x = rows[s.row]
        opening = encode_open(s.sid, MODE_PIECES if pieces else MODE_RAW,
                              s.seed)
        data = []
        for i in range(n_win):
            lo, hi = i * w, (i + 1) * w
            if pieces:
                idx = np.nonzero(comp["emit"][s.row, lo:hi])[0] + lo
                data.append(encode_data_pieces(
                    s.sid, float(x[0]), hi,
                    comp["endpoint"][s.row, idx].astype(np.float32),
                    idx.astype(np.int32)))
            else:
                data.append(encode_data_raw(s.sid, x[lo:hi]))
        closes = []
        for i in range(n_win):
            tail = None
            if pieces and bool(comp["tail_emit"][s.row, i]):
                tail = float(comp["tail_endpoint"][s.row, i])
            closes.append(encode_close(s.sid, (i + 1) * w, tail))
        out.append((opening, data, closes))
    return out


def _sensor_compress(rows, cfg, w):
    """The sensor's own compressor (the program's sender half, window by
    window as ``SenderClient`` runs it), on the CPU; the tail each window
    would flush if the series closed after it."""
    import jax.numpy as jnp

    from repro.core.compress import compressor_finalize
    from repro.core.symed import SymEDConfig, symed_encode_chunk

    sym = SymEDConfig(tol=cfg["tol"], alpha=cfg["alpha"],
                      len_max=cfg["len_max"], n_max=cfg["n_max"])
    state, emit, endpoint, tail_emit, tail_endpoint = None, [], [], [], []
    for lo in range(0, rows.shape[1], w):
        state, ev = symed_encode_chunk(jnp.asarray(rows[:, lo:lo + w]), sym,
                                       state)
        tail = compressor_finalize(state)
        emit.append(np.asarray(ev["emit"]))
        endpoint.append(np.asarray(ev["endpoint"]))
        tail_emit.append(np.asarray(tail.emit))
        tail_endpoint.append(np.asarray(tail.endpoint))
    return {"emit": np.concatenate(emit, 1),
            "endpoint": np.concatenate(endpoint, 1),
            "tail_emit": np.stack(tail_emit, 1),
            "tail_endpoint": np.stack(tail_endpoint, 1)}


def plan(spec):
    """Series, sessions and the send schedule of one run.

    Every run of a cell does the same work: the series, their order, each
    sensor's phase, the window of its first series it stands at when the
    measured window opens, and every session's digitizer seed come from
    the configuration's ``fleet_seed``.  The digitizer seed steers its
    random re-seeding, and so how often a step grows k; a step waits for
    its slowest lane, so one fleet's draw of digitizer seeds made the
    backlog cell serve 19.8 symbols/s and another's 47.5 (TPU v5e), while
    two runs of one draw agreed to the last symbol.  The run's seed draws
    only the sample that ``correct`` checks (``checks.sample``).

    Due times are seconds from the schedule's start; the fill's windows
    fall before it, one period apart, as if the sensor had been running.
    """
    w, length = spec["window_points"], spec["series_points"]
    n_win = length // w
    sensors = spec["sensors"]
    period = sensors * w / spec["offered_points_per_s"]
    horizon = RAMP_S + spec["seconds"]
    per_sensor = int(np.ceil((n_win + horizon / period) / n_win)) + 1
    n_rows = sensors * per_sensor
    fleet_seed = spec["cfg"]["fleet_seed"]
    rows, pieces = fleet.make_rows(n_rows + WARM_CANDIDATES, length,
                                   fleet_seed, spec["cfg"])
    # the warm-up only has to compile: its series are the calmest of a few
    # extra candidates, so its table steps are short
    calm = n_rows + np.argsort(pieces[n_rows:], kind="stable")
    rows = np.concatenate([rows[:n_rows], rows[calm[:WARM_SESSIONS]]])
    rng = np.random.default_rng(fleet_seed)
    phases = rng.uniform(0.0, period, sensors)
    filled = rng.integers(0, n_win, sensors)
    keys = rng.integers(0, 1 << 32, len(rows), dtype=np.uint64)
    warm = [Session(f"w{i}", n_rows + i, int(keys[n_rows + i]))
            for i in range(WARM_SESSIONS)]
    sessions = []
    for i in range(sensors):
        for k in range(per_sensor):
            first = k * n_win - int(filled[i])  # windows from the window's
            if phases[i] + first * period >= horizon:   # first due one
                break
            row = i * per_sensor + k
            s = Session(f"{i:03d}.{k}", row, int(keys[row]))
            s.dues = [phases[i] + (first + j) * period for j in range(n_win)]
            s.dues.append(s.dues[-1])
            s.filled = int(filled[i]) if k == 0 else 0
            sessions.append(s)
    return rows, warm, sessions, period


def main() -> int:
    spec = json.loads(sys.argv[1])
    t_setup = time.monotonic()
    rows, warm, sessions, period = plan(spec)
    # the sensor's compressor: the frames of pieces mode, and in both modes
    # when each symbol becomes due (its piece's completing window)
    comp = _sensor_compress(rows, spec["cfg"], spec["window_points"])
    frames = _frames(spec, rows, warm + sessions, comp)
    by_sid = {s.sid: s for s in warm + sessions}
    fr = {s.sid: f for s, f in zip(warm + sessions, frames)}
    w = spec["window_points"]

    conns = []
    for _ in range(CONNECTIONS):
        sock = socket.create_connection(("127.0.0.1", spec["port"]),
                                        timeout=60.0)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.setblocking(False)
        conns.append(Conn(sock))
    sel = selectors.DefaultSelector()
    for c in conns:
        sel.register(c.sock, selectors.EVENT_READ, c)

    from repro.core.receiver import unpack_delta_frame
    from repro.launch.transport import (
        CLOSED, DELTA, ERROR, FrameDecoder, decode_closed)

    decoders = {id(c): FrameDecoder() for c in conns}
    records = []          # (receipt, latency) per symbol
    unmatched = [0]       # endpoints found in no point of their series
    state = {"t0": 0.0}

    def on_frame(frame, now):
        s = by_sid.get(frame.sid)
        if frame.type == ERROR:
            if s is None:
                raise RuntimeError(f"server error: {frame.payload!r}")
            s.error = frame.payload.decode("utf-8", "replace")
            s.closed = True
            return
        if s is None:
            return
        if frame.type == DELTA:
            labels, endpoints = unpack_delta_frame(frame.payload)
            close = False
        elif frame.type == CLOSED:
            res = decode_closed(frame.payload)
            labels, endpoints = res["labels"], res["endpoints"]
            s.evicted, s.n_pieces, s.t_seen = (res["evicted"],
                                               res["n_pieces"], res["t_seen"])
            close = True
        else:
            return
        s.labels.append(labels)
        s.endpoints.append(endpoints)
        n = len(labels)
        s.received += n
        if n and s.dues:
            x = rows[s.row]
            last = len(s.dues) - 1
            for e in endpoints:
                # the piece ends at the raw point equal to its endpoint and
                # is cut while the next point is ingested: the window that
                # shipped that point completed it
                hit = np.nonzero(x[s.cursor:] == e)[0]
                if len(hit):
                    s.cursor += int(hit[0]) + 1
                else:
                    unmatched[0] += 1
                due = s.dues[min(s.cursor // w, last)]
                records.append((now, now - (state["t0"] + due)))
        if close:
            s.closed = True

    parent = []           # lines from the parent (stdin)
    sel.register(sys.stdin.fileno(), selectors.EVENT_READ, None)

    def pump(timeout):
        now = time.monotonic()
        got = False
        for key, mask in sel.select(timeout):
            got = True
            c = key.data
            if c is None:
                words = os.read(sys.stdin.fileno(), 4096)
                if not words:
                    raise ConnectionError("the parent closed stdin")
                parent.extend(words.decode().split())
                continue
            if mask & selectors.EVENT_WRITE:
                flush(c, now)
            if mask & selectors.EVENT_READ:
                try:
                    data = c.sock.recv(1 << 20)
                except BlockingIOError:
                    continue
                if not data:
                    raise ConnectionError("server closed a connection")
                for frame in decoders[id(c)].feed(data):
                    on_frame(frame, now)
        return got

    def await_parent(word):
        """Keep reading the sockets until the parent writes ``word``."""
        while word not in parent:
            pump(0.5)

    lags = []             # (due, send stamp) of every frame of the schedule

    def flush(c, now):
        if c.out:
            try:
                k = c.sock.send(c.out)
            except BlockingIOError:
                k = 0
            del c.out[:k]
            c.sent += k
        while c.mark_i < len(c.marks) and c.marks[c.mark_i][0] <= c.sent:
            lags.append((c.marks[c.mark_i][1], now))
            c.mark_i += 1
        want = selectors.EVENT_READ | (selectors.EVENT_WRITE if c.out else 0)
        sel.modify(c.sock, want, c)

    def queue(c, data, due):
        c.out += data
        c.queued += len(data)
        if due is not None:
            c.marks.append((c.queued, due))

    def await_all(done, what):
        deadline = time.monotonic() + WARM_TIMEOUT_S
        while not done():
            if time.monotonic() > deadline:
                raise TimeoutError(f"{what} never completed")
            for c in conns:
                if c.out:
                    flush(c, time.monotonic())
            pump(0.5)

    # ---- warm-up: closed loop, every frame at once, await every CLOSED ---
    t_prep = time.monotonic()
    for s in warm:
        opening, data, closes = fr[s.sid]
        queue(conns[0], opening + b"".join(data) + closes[-1], None)
    flush(conns[0], time.monotonic())
    await_all(lambda: all(s.closed for s in warm), "warm-up sessions")
    for s in warm:
        if s.error or s.evicted:
            raise RuntimeError(f"warm-up session {s.sid} failed: {s.error}")

    # ---- fill: every sensor's windows before the measured one, at once --
    t_fill = time.monotonic()
    filling = [s for s in sessions if s.filled]
    for s in filling:
        opening, data, _ = fr[s.sid]
        c = conns[int(s.sid.split(".")[0]) % len(conns)]
        queue(c, opening + b"".join(data[: s.filled]), None)
        s.sent = s.filled
    await_all(lambda: not any(c.out for c in conns), "sending the fill")
    print(f"FILL {sum(c.sent for c in conns)}", flush=True)
    await_parent("FILLED")
    while pump(0.0):      # the replies are all in the sockets by now
        pass
    # one symbol for every piece the sensor cut in those windows; a
    # shortfall is the server's, and ``correct`` judges it
    short = [s.sid for s in filling
             if s.received != int(comp["emit"][s.row, : s.filled * w].sum())]
    if short:
        print(f"loadgen: the fill left {len(short)} sessions short of "
              f"symbols, e.g. {short[:4]}", file=sys.stderr, flush=True)
    print(f"WARM {t_prep - t_setup:.6f} {t_fill - t_prep:.6f} "
          f"{time.monotonic() - t_fill:.6f}", flush=True)
    await_parent("GO")

    # ---- the open-loop schedule -----------------------------------------
    events = []
    for s in sessions:
        opening, data, closes = fr[s.sid]
        c = int(s.sid.split(".")[0]) % len(conns)
        for j in range(s.filled, s.windows(spec["seconds"])):
            last = j == len(data) - 1
            events.append((s.dues[j], c, s, (opening if j == 0 else b"")
                           + data[j] + (closes[-1] if last else b"")))
    events.sort(key=lambda e: e[0])
    t0 = time.monotonic() + 0.2
    state["t0"] = t0
    win0 = t0 + RAMP_S
    win1 = win0 + spec["seconds"]
    print(f"WINDOW {win0:.6f} {win1:.6f}", flush=True)
    i = 0
    while i < len(events) or time.monotonic() < win1:
        now = time.monotonic()
        while i < len(events) and t0 + events[i][0] <= now:
            due, c, s, data = events[i]
            queue(conns[c], data, t0 + due)
            s.sent += 1
            flush(conns[c], now)
            i += 1
        nxt = t0 + events[i][0] if i < len(events) else win1
        pump(max(0.0, min(nxt - time.monotonic(), 0.05)))
    # the window is over: every series still open closes after the windows
    # it sent (the sensor flushes its open segment, as at any close)
    for s in sessions:
        if 0 < s.sent < len(fr[s.sid][1]):
            c = int(s.sid.split(".")[0]) % len(conns)
            s.dues[s.sent] = win1 - t0
            del s.dues[s.sent + 1:]
            queue(conns[c], fr[s.sid][2][s.sent - 1], None)
            flush(conns[c], time.monotonic())
    deadline = max(time.monotonic(), win1) + DRAIN_TIMEOUT_S
    while not all(s.closed for s in sessions):
        if time.monotonic() > deadline:
            break
        for c in conns:
            if c.out:
                flush(c, time.monotonic())
        pump(0.05)
    t_end = time.monotonic()
    for c in conns:
        c.sock.close()

    lat = np.asarray([d for t, d in records if win0 <= t <= win1],
                     np.float64)
    lag = np.asarray([sent - due for due, sent in lags
                      if win0 <= due <= win1], np.float64)
    done = [s for s in sessions if s.closed and not s.error and not s.evicted]
    # symbols due (their piece's completing window sent) against symbols
    # received, over the window: a backlog that grows is a load above
    # capacity
    due_t = []
    for s in sessions:
        steps = np.nonzero(comp["emit"][s.row, : s.sent * w])[0]
        due_t += [t0 + s.dues[j] for j in steps // w]
        if s.sent == len(fr[s.sid][1]):
            due_t.append(t0 + s.dues[-1])  # the flush at close
    due_t = np.sort(due_t)
    got_t = np.sort([t for t, _ in records])
    probes = np.linspace(win0, win1, int(spec["seconds"]) + 1)  # 1 s apart
    backlog = (np.searchsorted(due_t, probes, "right")
               - np.searchsorted(got_t, probes, "right"))
    due_in_window = int(np.sum((due_t >= win0) & (due_t <= win1)))
    record = {
        "window": [win0, win1], "period_s": period,
        "symbols_in_window": len(lat),
        "unmatched_endpoints": unmatched[0],
        "latencies_s": lat.tolist(),
        "lag_s": lag.tolist(),
        "sessions": [{
            "sid": s.sid, "row": s.row, "seed": s.seed,
            "first_due": t0 + s.dues[0], "last_due": t0 + s.dues[-1],
            "closed": s.closed, "error": s.error, "evicted": s.evicted,
            "n_pieces": s.n_pieces, "t_seen": s.t_seen,
            "points_sent": s.sent * w,
            "labels": (np.concatenate(s.labels).tolist()
                       if s.labels else []),
            "endpoints": (np.concatenate(s.endpoints).tolist()
                          if s.endpoints else []),
        } for s in sessions],
        "drained_s": t_end - win1,
        "symbol_backlog": backlog.tolist(),
        "symbols_due_in_window": due_in_window,
        "completed": len(done),
    }
    with open(spec["out"], "w") as f:
        json.dump(record, f)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())

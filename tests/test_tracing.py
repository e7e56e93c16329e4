"""Tracing of the served path: the table step's work counters, the serve
loop's spans, and the chip benchmark's readers that use them.

* ``TestDigitizeWork`` -- ``DigitizeWork`` on hand-built tables with known
  spans: trips are span lengths, the table and per-slot paths agree lane
  for lane, and ``lloyd_iters * (trips + rounds)`` is the number of Lloyd
  half-step calls the table step makes (a call-counting stub of the
  kernel entry point), and only lanes whose result the step keeps grow k.
* ``TestServedCounters`` -- the harvest span's arguments and the registry
  counters of a ``StreamServer``.
* ``TestServeLoopSpans`` -- a loopback ``TransportServer`` records
  ``transport.wait``, ``stream.open``, ``stream.close`` and
  ``transport.reply``, and the symbol latency from frame read to DELTA
  write.
* ``TestClockOffset`` -- a CPU profiler session: the benchmark's clock
  offset maps every ``symed.table_step*`` annotation into its dispatch.
* ``TestReaders`` -- ``lloyd_useful_share`` and ``idle_on_host_share`` on
  synthetic contexts with known answers, ``None`` without their inputs.
"""
import functools
import importlib.util
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_stream

from repro.core import digitize as dz
from repro.core.symed import SymEDConfig
from repro.kernels import ops
from repro.launch.stream import StreamServer
from repro.launch.transport import SenderClient, TransportServer, session_seed
from repro.obs import Observability, current

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
CFG = SymEDConfig(tol=0.5, alpha=0.02, scl=1.0, k_min=3, k_max=8,
                  len_max=32, n_max=64, lloyd_iters=5)
SPAN_KW = dict(tol=0.1, scl=1.0, k_min=3, k_max_active=8, lloyd_iters=3)


def _table(s, n_max, seed):
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.key(seed), s)
    state = jax.vmap(lambda kk: dz.digitizer_init(n_max, 8, kk))(keys)
    lengths = jnp.asarray(rng.integers(1, 9, size=(s, n_max)), jnp.float32)
    incs = jnp.asarray(rng.normal(0, 2, size=(s, n_max)), jnp.float32)
    return state, lengths, incs


def _assert_state_equal(a, b, msg):
    for name in a._fields:
        la, lb = getattr(a, name), getattr(b, name)
        if name == "key":
            la, lb = jax.random.key_data(la), jax.random.key_data(lb)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=f"{msg}: {name}")


class TestDigitizeWork:
    LO = (0, 2, 5, 0, 7, 3)
    HI = (9, 2, 14, 4, 16, 3)   # spans 9, 0, 9, 4, 9, 0

    def _spans(self):
        return (jnp.asarray(self.LO, jnp.int32),
                jnp.asarray(self.HI, jnp.int32))

    def test_trips_are_span_lengths_and_paths_agree(self):
        state, lengths, incs = _table(len(self.LO), 16, 3)
        lo, hi = self._spans()
        _, _, w_t = dz.digitize_span_table(state, lengths, incs, lo, hi,
                                           **SPAN_KW)
        _, _, w_s = jax.vmap(
            lambda st, le, ic, a, b: dz.digitize_span(st, le, ic, a, b,
                                                      **SPAN_KW)
        )(state, lengths, incs, lo, hi)
        span = np.asarray(self.HI) - np.asarray(self.LO)
        np.testing.assert_array_equal(np.asarray(w_t.trips), span)
        np.testing.assert_array_equal(np.asarray(w_s.trips), span)
        np.testing.assert_array_equal(np.asarray(w_t.growing_rounds),
                                      np.asarray(w_s.growing_rounds))
        # against a step-by-step count: a clustering step's growth rounds
        # are the centers it added beyond max(k, 1)
        step = jax.jit(functools.partial(dz.digitizer_step, **SPAN_KW))
        pieces = jnp.stack([lengths, incs], axis=-1)
        for s, (a, b) in enumerate(zip(self.LO, self.HI)):
            st = jax.tree.map(lambda leaf: leaf[s], state)
            grew = 0
            for j in range(a, b):
                st2, _ = step(st, pieces[s, j])
                if int(st.n) + 1 > SPAN_KW["k_min"]:
                    grew += int(st2.k) - max(int(st.k), 1)
                st = st2
            assert int(w_t.growing_rounds[s]) == grew, s
        # a slot's own loop runs exactly the rounds it grows in
        np.testing.assert_array_equal(np.asarray(w_s.rounds_run),
                                      np.asarray(w_s.growing_rounds))
        # the table's loop: one count for every lane, at least any lane's
        rounds = np.asarray(w_t.rounds_run)
        assert (rounds == rounds[0]).all()
        assert rounds[0] >= np.asarray(w_t.growing_rounds).max() > 0
        assert (np.asarray(w_t.growing_rounds)[span == 0] == 0).all()

    def test_lloyd_calls_are_iters_times_trips_plus_rounds(self, monkeypatch):
        calls = []

        def counting(coords, mask, centers, center_active):
            jax.debug.callback(lambda: calls.append(1))
            return jax.vmap(dz._lloyd_half_step)(coords, mask, centers,
                                                 center_active)

        monkeypatch.setattr(ops, "kmeans_assign", counting)
        state, lengths, incs = _table(len(self.LO), 16, 4)
        lo, hi = self._spans()
        _, _, work = dz.digitize_span_table(state, lengths, incs, lo, hi,
                                            use_kernel=True, **SPAN_KW)
        jax.effects_barrier()
        trips = int(np.asarray(work.trips).max())
        rounds = int(np.asarray(work.rounds_run)[0])
        assert rounds > 0
        assert len(calls) == SPAN_KW["lloyd_iters"] * (trips + rounds)

    def test_counters_leave_results_bitwise_unchanged(self):
        """The same symbols and state as before the counters: split spans
        resume to the one-pass result, and the work adds up."""
        state, lengths, incs = _table(4, 12, 5)
        lo = jnp.zeros((4,), jnp.int32)
        mid = jnp.asarray([3, 0, 6, 12], jnp.int32)
        hi = jnp.asarray([7, 5, 6, 12], jnp.int32)
        st1, sy1, w1 = dz.digitize_span_table(state, lengths, incs, lo, hi,
                                              **SPAN_KW)
        sta, _, wa = dz.digitize_span_table(state, lengths, incs, lo, mid,
                                            **SPAN_KW)
        stb, _, wb = dz.digitize_span_table(sta, lengths, incs, mid, hi,
                                            **SPAN_KW)
        np.testing.assert_array_equal(np.asarray(stb.labels),
                                      np.asarray(st1.labels))
        np.testing.assert_array_equal(np.asarray(stb.k), np.asarray(st1.k))
        np.testing.assert_array_equal(
            np.asarray(wa.trips) + np.asarray(wb.trips), np.asarray(w1.trips))
        np.testing.assert_array_equal(
            np.asarray(wa.growing_rounds) + np.asarray(wb.growing_rounds),
            np.asarray(w1.growing_rounds))

    @pytest.mark.parametrize("seed", (11, 12))
    @pytest.mark.parametrize("use_kernel", (False, True),
                             ids=("reference", "kernel"))
    def test_growth_runs_for_kept_lanes_only(self, use_kernel, seed):
        """Lanes whose clustering result the step drops grow no round: a
        lane past its span with ``n > k_min``, a lane in the trivial phase
        and an empty slot leave the rounds and the live lanes' results as
        a table of the live lanes alone has them."""
        state, lengths, incs = _table(5, 16, seed)
        zero = jnp.zeros((5,), jnp.int32)
        ahead = jnp.asarray([0, 6, 0, 0, 0], jnp.int32)
        state, _, _ = dz.digitize_span_table(state, lengths, incs, zero,
                                             ahead, **SPAN_KW)
        # lane 0 grows, 1 is past its span, 2 stays trivial, 3 is an empty
        # slot, 4 runs out of its span while lane 0 goes on
        lo = ahead
        hi = jnp.asarray([10, 6, 2, 0, 5], jnp.int32)
        run = functools.partial(dz.digitize_span_table, use_kernel=use_kernel,
                                **SPAN_KW)
        st, sy, work = run(state, lengths, incs, lo, hi)

        # lane 1's phantom piece (at its clamped cursor) would grow k
        lane1 = jax.tree.map(lambda leaf: leaf[1], state)
        ghost, _ = dz.digitizer_step(lane1, jnp.stack(
            [lengths[1, 6], incs[1, 6]]), **SPAN_KW)
        assert int(ghost.k) > max(int(lane1.k), 1)

        rounds = int(np.asarray(work.rounds_run)[0])
        assert 0 < rounds <= int(np.asarray(work.growing_rounds).sum())

        live = np.asarray([0, 2, 4])

        def sub(tree):
            return jax.tree.map(lambda leaf: leaf[live], tree)

        st_l, sy_l, work_l = run(sub(state), lengths[live], incs[live],
                                 lo[live], hi[live])
        assert int(np.asarray(work_l.rounds_run)[0]) == rounds
        _assert_state_equal(sub(st), st_l, "live lanes alone")
        np.testing.assert_array_equal(np.asarray(sy)[live], np.asarray(sy_l))

        st_v, sy_v, _ = jax.vmap(
            lambda s, le, ic, a, b: dz.digitize_span(s, le, ic, a, b,
                                                     **SPAN_KW)
        )(state, lengths, incs, lo, hi)
        if use_kernel:  # the kernel leaves 0 where the reference leaves
            # whatever a label past n held: compare the pieces' labels
            past = np.arange(16)[None, :] >= np.asarray(st_v.n)[:, None]
            st, st_v = (x._replace(labels=jnp.where(past, 0, x.labels))
                        for x in (st, st_v))
        _assert_state_equal(st, st_v, "vmapped digitize_span")
        np.testing.assert_array_equal(np.asarray(sy), np.asarray(sy_v))


def _events(obs, prefix):
    return [(n, t0, d, a) for n, ph, t0, d, a in obs.tracer.events()
            if ph == "X" and n.startswith(prefix)]


class TestServedCounters:
    @pytest.mark.parametrize("mode", ("raw", "pieces"))
    def test_harvest_args_and_counters(self, rng, mode):
        obs = Observability()
        srv = StreamServer(CFG, max_sessions=4, window_cap=32,
                           digitize_every_k=1, obs=obs)
        sids = ["a", "b", "c"]
        for s in sids:
            srv.open(s)
        if mode == "raw":
            srv.ingest_many({s: make_stream(rng, 96) for s in sids})
        else:
            for s in sids:
                x = make_stream(rng, 96)
                srv.ingest_pieces_many({s: {
                    "endpoints": x[8::8], "steps": np.arange(8, 96, 8),
                    "t_seen": 96, "t0": float(x[0])}})
        harvests = _events(obs, "stream.harvest")
        assert harvests
        for _, _, _, a in harvests:
            assert a["lane_runs"] == srv.capacity * (a["trips"] + a["rounds"])
            assert 0 < a["useful_runs"] <= a["lane_runs"]
            assert a["slowest"] in sids or a["slowest_rounds"] == 0
            # every growth round runs for a kept lane that grows in it
            assert a["slowest_rounds"] > 0 or a["rounds"] == 0
        counters = obs.snapshot()["counters"]
        total = lambda k: sum(a[k] for *_, a in harvests)  # noqa: E731
        assert counters["symed_digitize_trips_total"] == total("trips")
        assert counters["symed_kgrowth_rounds_total"] == total("rounds")
        assert counters["symed_lloyd_lane_runs_total{kind=executed}"] \
            == total("lane_runs")
        assert counters["symed_lloyd_lane_runs_total{kind=useful}"] \
            == total("useful_runs")

    def test_direct_ingest_latency_runs_from_the_call(self, rng):
        obs = Observability()
        srv = StreamServer(CFG, max_sessions=2, window_cap=32, obs=obs)
        srv.open("s")
        t0 = time.perf_counter_ns()
        out = srv.ingest("s", make_stream(rng, 96))
        wall = time.perf_counter_ns() - t0
        hist = obs.metrics.histogram("symed_symbol_latency_seconds")
        assert hist.count == out["n_new"] > 0
        assert 0 < hist.total <= wall * out["n_new"]


class TestServeLoopSpans:
    def test_loopback_records_wait_open_close_reply(self, rng):
        obs = Observability(trace_capacity=4096)
        srv = StreamServer(CFG, max_sessions=4, window_cap=32,
                           digitize_every_k=1, obs=obs)
        transport = TransportServer(srv, port=0)
        thread = threading.Thread(target=transport.serve,
                                  kwargs={"expect_sessions": 2,
                                          "poll": 0.005}, daemon=True)
        thread.start()
        client = SenderClient("127.0.0.1", transport.port, CFG, mode="pieces")
        try:
            for sid in ("w0", "w1"):
                client.open(sid, session_seed(sid, 3))
            time.sleep(0.05)  # the loop polls with nothing to read
            for sid in ("w0", "w1"):
                client.send(sid, make_stream(rng, 96))
            for sid in ("w0", "w1"):
                client.close(sid)
        finally:
            client.shutdown()
            thread.join(timeout=60)
        t_end = time.perf_counter_ns()
        assert not thread.is_alive()
        names = {n for n, *_ in _events(obs, "")}
        assert {"transport.wait", "stream.open", "stream.close",
                "transport.reply"} <= names
        waits = _events(obs, "transport.wait")
        # one span per stretch of polls, not one per poll
        assert any(a["polls"] > 1 for *_, a in waits)
        assert len(waits) < sum(a["polls"] for *_, a in waits)
        # symbols of DELTA frames, timed from the read of their frame
        hist = obs.metrics.histogram("symed_symbol_latency_seconds")
        assert hist.count > 0
        decode = min(t0 for _, t0, _, _ in _events(obs, "transport.decode"))
        assert 0 < hist.total <= hist.count * (t_end - decode)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"_chip_{name}", CHIP / name)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestClockOffset:
    def test_annotations_map_inside_their_dispatch(self, rng, tmp_path):
        sys.path.insert(0, str(CHIP))
        try:
            trace_reduce = _load("trace_reduce.py")
        finally:
            sys.path.remove(str(CHIP))
        obs = Observability(jax_annotate=True)
        srv = StreamServer(CFG, max_sessions=4, window_cap=32, obs=obs)
        for s in ("a", "b"):
            srv.open(s)
        srv.ingest_many({s: make_stream(rng, 64) for s in ("a", "b")})
        t0 = time.monotonic()
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(3):
                srv.ingest_many({s: make_stream(rng, 64) for s in ("a", "b")})
        finally:
            jax.profiler.stop_trace()
        t1 = time.monotonic()
        ctx = trace_reduce.Context(tmp_path, (t0, t1), obs.tracer.events(),
                                   cfg={}, loadgen={}, device_kind="cpu")
        assert len(ctx.annotations) >= 6
        off = ctx._clock_offset()
        assert off is not None
        disp = [(a, b) for n, a, b in ctx.spans
                if n.startswith("stream.dispatch")]
        for _, a0, a1 in ctx.annotations:
            mid = (a0 + a1) / 2 - off
            assert any(d0 <= mid <= d1 for d0, d1 in disp), (mid, disp)


class _Ctx:
    """The part of ``trace_reduce.Context`` the two readers read."""

    def __init__(self, window, devices=(), offset=None):
        self.window = window
        self.window_s = window[1] - window[0]
        self.devices = list(devices)
        self._off = offset

    def _clock_offset(self):
        return self._off


def _span(obs, name, t0_s, t1_s, args=None):
    obs.tracer.add_span(name, int(t0_s * 1e9), int(t1_s * 1e9), args)


class TestReaders:
    def test_lloyd_useful_share(self):
        read = _load("metrics/lloyd_useful_share.py").read
        obs = Observability()
        _span(obs, "stream.harvest_pieces", 10.5, 10.6,
              {"lane_runs": 400, "useful_runs": 30})
        _span(obs, "stream.harvest_pieces", 11.0, 11.2,
              {"lane_runs": 600, "useful_runs": 20})
        _span(obs, "stream.harvest_pieces", 30.0, 30.2,  # after the window
              {"lane_runs": 600, "useful_runs": 600})
        assert current() is obs
        assert read(_Ctx((10.0, 20.0))) == pytest.approx(5.0)
        # no harvest counts in the window, as on a program without them
        assert read(_Ctx((40.0, 50.0))) is None
        Observability()
        assert read(_Ctx((10.0, 20.0))) is None

    def test_idle_on_host_share(self):
        read = _load("metrics/idle_on_host_share.py").read
        obs = Observability()
        off = 100.0  # trace clock = recorder clock + 100 s
        # window [10, 20]: device idle at [10, 11) (leading edge), gaps at
        # [13, 15) and [16, 16.5) (trace clock +100), trailing [19.5, 20]
        dev = {"busy_s": 10.0 - 1.0 - 2.0 - 0.5 - 0.5,
               "gaps": [(113.0, 115.0), (116.0, 116.5)]}
        _span(obs, "stream.dispatch_pieces", 11.0, 11.1)
        _span(obs, "stream.harvest_pieces", 12.9, 13.0)
        _span(obs, "stream.dispatch_pieces", 15.0, 15.1)
        _span(obs, "stream.harvest_pieces", 19.4, 19.5)
        # waits: before the window into its edge, over the first gap, and
        # none over the second gap or the trailing edge
        _span(obs, "transport.wait", 9.0, 10.6)
        _span(obs, "transport.wait", 13.0, 14.5)
        ctx = _Ctx((10.0, 20.0), [dev], offset=off)
        # idle 4.0 s, of it waiting 0.6 + 1.5 s: host 1.9 s of 10 s
        assert read(ctx) == pytest.approx(19.0)
        device_idle = 100.0 * (1.0 - dev["busy_s"] / ctx.window_s)
        assert read(ctx) <= device_idle

    def test_idle_on_host_share_edges_follow_the_step_in_flight(self):
        read = _load("metrics/idle_on_host_share.py").read
        obs = Observability()
        dev = {"busy_s": 9.0, "gaps": []}  # 1 s of edge idle, no gaps
        _span(obs, "stream.dispatch", 9.5, 9.6)
        _span(obs, "stream.harvest", 19.0, 19.1)
        _span(obs, "transport.wait", 9.0, 10.5)
        # busy at the start: the idle is the trailing second, not waited
        assert read(_Ctx((10.0, 20.0), [dev], offset=0.0)) \
            == pytest.approx(10.0)

    def test_idle_on_host_share_absent_inputs(self):
        read = _load("metrics/idle_on_host_share.py").read
        dev = {"busy_s": 9.0, "gaps": []}
        obs = Observability()
        _span(obs, "stream.dispatch", 10.5, 10.6)
        # no transport.wait span: a program that does not record them
        assert read(_Ctx((10.0, 20.0), [dev], offset=0.0)) is None
        _span(obs, "transport.wait", 10.0, 10.5)
        assert read(_Ctx((10.0, 20.0), [], offset=0.0)) is None  # no chip
        assert read(_Ctx((10.0, 20.0), [dev], offset=None)) is None

"""Flight-recorder battery: metrics math, span ring, exposition, and wiring.

Five layers:

* ``TestBuckets`` / ``TestHistogram`` -- the log-bucket scheme and the
  bucket-derived quantiles, checked against numpy ground truth (the
  recorder's p50/p99/p999 must track real quantiles within the bucket
  width bound, not just be self-consistent).
* ``TestSpanRing`` -- ring wraparound accounting and Chrome trace-event
  JSON schema validity (the document must load in Perfetto unmodified).
* ``TestRegistry`` / ``TestPrometheus`` -- get-or-create vs callback
  registration semantics and the text exposition format (cumulative
  monotone buckets, ``+Inf`` == count, derived quantile gauges).
* ``TestServingIntegration`` -- a loopback ``TransportServer`` scraped
  over HTTP mid-process: the ``/metrics`` text and ``/metrics.json``
  snapshot must agree with the stream server's own ``report``; and the
  recorder must be *inert* when disabled (bitwise-identical deltas,
  no ``"obs"`` report key).
* ``TestSweepInclusion`` -- ``src/repro/obs`` is inside the symlint
  sweep, so the zero-host-sync hot-path contract is machine-checked.
"""
import json
import threading
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from conftest import make_stream

from repro.core.symed import SymEDConfig
from repro.launch.stream import StreamServer
from repro.launch.transport import SenderClient, TransportServer, session_seed
from repro.obs import Observability, as_obs, disabled
from repro.obs.metrics import (
    N_BUCKETS, Histogram, MetricsRegistry, NULL_INSTRUMENT,
    bucket_bounds, bucket_index,
)
from repro.obs.tracing import SpanTracer, annotate
from repro.obs.export import PROM_CONTENT_TYPE, ObsHTTPServer, prometheus_text

CFG = SymEDConfig(tol=0.5, alpha=0.02, scl=1.0, k_min=3, k_max=8,
                  len_max=32, n_max=64, lloyd_iters=5)


# ------------------------------------------------------------- bucket scheme


class TestBuckets:
    def test_bounds_partition_the_line(self):
        """Buckets tile [0, inf): hi of bucket i is lo of bucket i+1, and
        the lower bound maps back to its own index."""
        prev_hi = 0
        for i in range(2048):
            lo, hi = bucket_bounds(i)
            assert lo == prev_hi, i
            assert hi > lo, i
            assert bucket_index(lo) == i
            assert bucket_index(hi - 1) == i
            assert bucket_index(hi) == i + 1
            prev_hi = hi

    def test_index_monotone_and_value_in_bounds(self):
        rng = np.random.default_rng(42)
        vals = sorted(int(v) for v in
                      np.concatenate([rng.integers(0, 1 << b, size=64)
                                      for b in (4, 10, 20, 32, 48, 62)]))
        prev = -1
        for v in vals:
            i = bucket_index(v)
            lo, hi = bucket_bounds(i)
            assert lo <= v < hi
            assert i >= prev  # monotone in value
            prev = i

    def test_relative_width_bound(self):
        """Each bucket spans <= 25% of its lower bound (quantile error
        bound) once past the exact unit buckets."""
        for i in range(4, 2048):
            lo, hi = bucket_bounds(i)
            assert (hi - lo) * 4 <= lo

    def test_covers_64bit_nanoseconds(self):
        assert bucket_index((1 << 63) - 1) < N_BUCKETS


# ---------------------------------------------------------------- histogram


class TestHistogram:
    def test_quantiles_vs_numpy(self):
        """Bucket-midpoint quantiles track numpy within the bucket width
        bound on a heavy-tailed latency-like distribution."""
        rng = np.random.default_rng(7)
        samples = np.exp(rng.normal(12.0, 1.2, size=20000)).astype(np.int64)
        h = Histogram("t", unit="ns")
        for v in samples:
            h.observe(int(v))
        for q in (0.5, 0.9, 0.99, 0.999):
            got = h.quantile(q)
            want = float(np.quantile(samples, q))
            assert abs(got - want) / want < 0.15, (q, got, want)

    def test_empty_and_single(self):
        h = Histogram("t")
        assert h.quantile(0.5) == 0.0
        assert h.mean == 0.0
        h.observe(1000)
        lo, hi = bucket_bounds(bucket_index(1000))
        assert h.quantile(0.5) == (lo + hi) / 2.0
        assert h.quantile(0.999) == (lo + hi) / 2.0
        assert h.count == 1 and h.total == 1000

    def test_observe_n_equals_repeated_observe(self):
        a, b = Histogram("a"), Histogram("b")
        for v in (3, 77, 1 << 20):
            a.observe_n(v, 5)
            for _ in range(5):
                b.observe(v)
        assert a.buckets == b.buckets
        assert (a.count, a.total) == (b.count, b.total)
        a.observe_n(123, 0)  # no-op
        assert a.count == b.count

    def test_negative_clamped_to_zero(self):
        h = Histogram("t")
        h.observe(-5)
        assert h.buckets[0] == 1 and h.total == 0


# ---------------------------------------------------------------- span ring


class TestSpanRing:
    def test_wraparound_keeps_newest_oldest_first(self):
        tr = SpanTracer(capacity=8)
        for i in range(20):
            tr.instant(f"ev{i}")
        assert tr.recorded == 20
        assert tr.dropped == 12
        evs = tr.events()
        assert [e[0] for e in evs] == [f"ev{i}" for i in range(12, 20)]
        ts = [e[2] for e in evs]
        assert ts == sorted(ts)  # oldest first

    def test_under_capacity_no_drops(self):
        tr = SpanTracer(capacity=8)
        for i in range(5):
            tr.instant(f"ev{i}")
        assert tr.dropped == 0
        assert [e[0] for e in tr.events()] == [f"ev{i}" for i in range(5)]

    def test_disabled_records_nothing(self):
        tr = SpanTracer(capacity=8, enabled=False)
        tr.instant("x")
        tr.add("y", 0)
        with tr.span("z"):
            pass
        assert tr.recorded == 0 and tr.events() == []

    def test_span_context_manager(self):
        tr = SpanTracer(capacity=8)
        with tr.span("work", {"k": 1}):
            pass
        (name, ph, _, dur, args), = tr.events()
        assert (name, ph, args) == ("work", "X", {"k": 1})
        assert dur >= 0

    def test_chrome_trace_schema(self, tmp_path):
        """The written document is valid Chrome trace-event JSON: list of
        events with name/ph/ts/pid/tid, durations on X, scope on i."""
        tr = SpanTracer(capacity=16, pid=7)
        t0 = tr._t0_ns
        tr.add_span("dispatch", t0 + 1000, t0 + 51000, {"rounds": 2})
        tr.instant("grow", {"capacity": 4})
        path = tmp_path / "trace.json"
        tr.write(str(path), tid=3)
        doc = json.loads(path.read_text())
        assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
        assert doc["otherData"]["dropped_events"] == 0
        evs = doc["traceEvents"]
        assert len(evs) == 2
        for ev in evs:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
            assert (ev["pid"], ev["tid"]) == (7, 3)
            assert ev["ts"] >= 0.0
        span, instant = evs
        assert span["ph"] == "X" and span["dur"] == pytest.approx(50.0)
        assert span["ts"] == pytest.approx(1.0)  # relative to tracer epoch
        assert span["args"] == {"rounds": 2}
        assert instant["ph"] == "i" and instant["s"] == "t"

    def test_annotate_is_context_manager(self):
        with annotate("symed.table_step"):
            pass  # must not raise, with or without a live profiler


# ----------------------------------------------------------------- registry


class TestRegistry:
    def test_value_instruments_get_or_create(self):
        m = MetricsRegistry()
        c1 = m.counter("x_total", "help")
        c2 = m.counter("x_total")
        assert c1 is c2
        assert m.counter("x_total", labels={"mode": "raw"}) is not c1

    def test_kind_mismatch_raises(self):
        m = MetricsRegistry()
        m.counter("x_total")
        with pytest.raises(ValueError, match="already registered"):
            m.gauge("x_total")

    def test_callback_duplicates_refused(self):
        m = MetricsRegistry()
        m.counter_fn("cb_total", "h", lambda: 1.0)
        with pytest.raises(ValueError, match="already registered"):
            m.counter_fn("cb_total", "h", lambda: 2.0)

    def test_disabled_registry_hands_out_null(self):
        m = MetricsRegistry(enabled=False)
        h = m.histogram("t")
        assert h is NULL_INSTRUMENT
        h.observe(5)  # all no-ops
        assert m.counter_fn("c", "h", lambda: 1.0) is NULL_INSTRUMENT
        assert m.instruments() == []

    def test_snapshot_shape_and_units(self):
        m = MetricsRegistry()
        m.counter("c_total").inc(3)
        m.gauge("g").set(1.5)
        h = m.histogram("lat_seconds", unit="ns")
        h.observe(2_000_000)  # 2 ms
        snap = m.snapshot()
        assert snap["counters"] == {"c_total": 3.0}
        assert snap["gauges"] == {"g": 1.5}
        d = snap["histograms"]["lat_seconds"]
        assert d["count"] == 1.0
        assert d["sum"] == pytest.approx(2e-3)
        assert 1e-3 < d["p50"] < 4e-3  # scaled to seconds


# --------------------------------------------------------------- exposition


class TestPrometheus:
    def test_exposition_format(self):
        m = MetricsRegistry()
        m.counter("req_total", "requests", labels={"mode": "raw"}).inc(4)
        m.gauge("conns", "open connections").set(2)
        h = m.histogram("lat_seconds", "latency", unit="ns")
        for v in (100, 100, 5000, 90000):
            h.observe(v)
        text = prometheus_text(m)
        lines = text.splitlines()
        assert "# TYPE req_total counter" in lines
        assert 'req_total{mode="raw"} 4' in lines
        assert "# TYPE conns gauge" in lines
        assert "conns 2" in lines
        assert "# HELP lat_seconds latency" in lines
        assert "# TYPE lat_seconds histogram" in lines
        assert "lat_seconds_count 4" in lines
        # derived quantile gauges are grep-able without PromQL
        for q in ("p50", "p99", "p999"):
            assert any(line.startswith(f"lat_seconds_{q} ") for line in lines)

    def test_buckets_cumulative_and_inf_equals_count(self):
        m = MetricsRegistry()
        h = m.histogram("lat_seconds", unit="ns")
        rng = np.random.default_rng(3)
        for v in rng.integers(1, 1 << 30, size=500):
            h.observe(int(v))
        text = prometheus_text(m)
        cums, les = [], []
        for line in text.splitlines():
            if not line.startswith("lat_seconds_bucket"):
                continue
            lbl, val = line.rsplit(" ", 1)
            cums.append(int(val))
            le = lbl.split('le="', 1)[1].rstrip('"}')
            les.append(float("inf") if le == "+Inf" else float(le))
        assert cums == sorted(cums)  # cumulative monotone
        assert les == sorted(les)    # ascending upper bounds
        assert cums[-1] == 500 and les[-1] == float("inf")


# -------------------------------------------------- loopback serving scrape


class _Loopback:
    """A served StreamServer on 127.0.0.1 with a deterministic shutdown."""

    def __init__(self, expect_sessions, **server_kw):
        kw = dict(max_sessions=4, window_cap=32, digitize_every_k=1)
        kw.update(server_kw)
        self.stream = StreamServer(CFG, **kw)
        self.transport = TransportServer(self.stream, port=0)
        self.thread = threading.Thread(
            target=self.transport.serve,
            kwargs={"expect_sessions": expect_sessions}, daemon=True)
        self.thread.start()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.thread.join(timeout=60)
        assert not self.thread.is_alive(), "transport server failed to exit"


def _http_get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.headers.get("Content-Type"), resp.read().decode()


def _prom_value(text, series):
    for line in text.splitlines():
        if line.startswith(series + " "):
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(f"series {series!r} not in exposition:\n{text}")


class TestServingIntegration:
    def test_loopback_scrape_matches_report(self, rng):
        """Drive real senders over a socket, scrape /metrics over HTTP, and
        require the exposition to agree with the server's own report."""
        obs = Observability(trace_capacity=256)
        streams = {f"obs-{i}": make_stream(rng, 96) for i in range(3)}
        sids = list(streams)
        with _Loopback(expect_sessions=len(sids), obs=obs) as lb:
            exporter = ObsHTTPServer(obs, port=0)
            try:
                client = SenderClient("127.0.0.1", lb.transport.port, CFG,
                                      mode="raw")
                for sid in sids:
                    client.open(sid, session_seed(sid, 5))
                    client.send(sid, streams[sid])
                results = {sid: client.close(sid) for sid in sids}
                assert all(r["t_seen"] == 96 for r in results.values())
                ctype, text = _http_get(exporter.url + "/metrics")
                assert ctype == PROM_CONTENT_TYPE
                _, snap_raw = _http_get(exporter.url + "/metrics.json")
                snap = json.loads(snap_raw)
                _, trace_raw = _http_get(exporter.url + "/trace")
            finally:
                client.shutdown()
                exporter.close()

        rep = lb.stream.report(wall_seconds=1.0)
        # stream-side series agree with the report totals
        assert _prom_value(text, "symed_points_in_total") == rep["points_in"]
        assert _prom_value(text, "symed_symbols_out_total") == rep["symbols_out"]
        assert _prom_value(text, "symed_frames_out_total") == rep["frames_out"]
        assert _prom_value(text, "symed_sessions_opened_total") == len(sids)
        assert _prom_value(text, "symed_sessions_closed_total") == len(sids)
        # transport-side series agree with the transport's own counts
        assert _prom_value(
            text, 'transport_frames_in_total{type="open"}') == len(sids)
        assert _prom_value(
            text, 'transport_frames_in_total{type="close"}') == len(sids)
        assert _prom_value(text, 'transport_frames_in_total{type="data"}') > 0
        assert _prom_value(text, "transport_rx_bytes_total") > 0
        assert _prom_value(text, "transport_tx_bytes_total") > 0
        # the paper's per-symbol latency instrument is populated (close-path
        # flushes have no arrival stamp, so count <= symbols_out)
        lat_count = _prom_value(text, "symed_symbol_latency_seconds_count")
        assert 0 < lat_count <= rep["symbols_out"]
        p99 = _prom_value(text, "symed_symbol_latency_seconds_p99")
        assert p99 > 0.0
        # the JSON snapshot endpoint mirrors the report's obs merge
        assert rep["obs"]["counters"]["symed_points_in_total"] \
            == snap["counters"]["symed_points_in_total"]
        assert snap["histograms"]["symed_symbol_latency_seconds"]["p99"] > 0
        assert snap["spans_recorded"] > 0
        # the trace endpoint serves loadable Chrome trace events
        trace = json.loads(trace_raw)
        names = {ev["name"] for ev in trace["traceEvents"]}
        assert "stream.dispatch" in names or "stream.harvest" in names

    def test_disabled_obs_is_inert_and_bitwise_identical(self, rng):
        """obs=False must cost nothing *and* change nothing: same deltas,
        no report key, shared null instruments."""
        ts = make_stream(rng, 96)
        outs = {}
        for flag in (True, False):
            srv = StreamServer(CFG, max_sessions=2, window_cap=32,
                               digitize_every_k=1, obs=flag)
            srv.open("s0")
            srv.ingest("s0", ts)
            outs[flag] = srv.close("s0")
            rep = srv.report(wall_seconds=1.0)
            if flag:
                assert "obs" in rep
            else:
                assert "obs" not in rep
                assert not srv.obs.enabled
                assert srv.obs is disabled()
        np.testing.assert_array_equal(outs[True]["delta"]["labels"],
                                      outs[False]["delta"]["labels"])
        np.testing.assert_array_equal(outs[True]["delta"]["endpoints"],
                                      outs[False]["delta"]["endpoints"])
        assert outs[True]["symbols"] == outs[False]["symbols"]

    def test_as_obs_normalization(self):
        bundle = Observability()
        assert as_obs(bundle) is bundle
        assert as_obs(False) is disabled()
        fresh_a, fresh_b = as_obs(None), as_obs(True)
        assert fresh_a.enabled and fresh_b.enabled
        assert fresh_a is not fresh_b  # per-server registries never collide

    def test_two_servers_never_collide_on_callbacks(self):
        """Each StreamServer gets its own registry by default, so callback
        registration (which refuses duplicates) stays safe."""
        a = StreamServer(CFG, max_sessions=2, window_cap=32)
        b = StreamServer(CFG, max_sessions=2, window_cap=32)
        assert a.obs is not b.obs


# ------------------------------------------------------------ symlint sweep


class TestSweepInclusion:
    def test_obs_files_inside_default_sweep(self):
        """src/repro/obs is covered by the symlint sweep, so the hot-path
        contract (no device syncs in recording paths) is machine-checked."""
        from repro.analysis.cli import find_root
        from repro.analysis.engine import DEFAULT_SWEEP, load_project

        root = find_root(Path(__file__).resolve().parent)
        project = load_project(root, [root / p for p in DEFAULT_SWEEP
                                      if (root / p).exists()])
        rels = set(project.files)
        for mod in ("metrics", "tracing", "export", "__init__"):
            assert f"src/repro/obs/{mod}.py" in rels

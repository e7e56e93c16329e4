"""Workload-harness battery: trace schema, scenario zoo, legacy shims,
SLO gates, and replay determinism.

The heart of the contract: a ``workload_trace/v1`` trace plus a seed is a
complete description of a run.  Replaying it twice -- in-process or over
the loopback transport, on 1 or 4 forced host devices -- must produce
bitwise-identical delta streams and identical schedule-determined counter
totals.  The legacy ``--arrival-pattern`` shims must synthesize the exact
tick schedule the retired ``launch.stream._arrival_schedule`` generator
yielded (compared against a frozen copy of it below).
"""
import json
import os
import subprocess
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro.workload import (
    KNOWN_SLOS, SCENARIOS, Trace, TraceBuilder, Workload, check_slos,
    legacy_arrival_schedule, parse_slo, parse_slo_specs, scenario_seed,
    synthesize,
)

REPO = Path(__file__).resolve().parents[1]
SUBENV = {**os.environ, "PYTHONPATH": str(REPO / "src")}


# ------------------------------------------------------------ trace schema


class TestTraceSchema:
    def _small(self):
        b = TraceBuilder("t", 0, 2, 64, 32)
        b.open(0, "a", 0)
        b.open(0, "b", 1, mode="pieces")
        b.data(0, "a", 0)
        b.data(10, "a", 1)
        b.data(10, "b", 0)
        b.close(10, "a")
        b.data(20, "b", 1)
        b.close(20, "b")
        return b.build()

    def test_roundtrip_preserves_digest(self, tmp_path):
        tr = self._small()
        path = tmp_path / "t.jsonl"
        tr.save(str(path))
        tr2 = Trace.load(str(path))
        assert tr2.digest() == tr.digest()
        assert tr2.sessions == tr.sessions
        assert tr2.events == tr.events

    def test_counts_and_ticks(self):
        tr = self._small()
        assert tr.counts() == {"events": 8, "windows": 4, "sessions": 2}
        ticks = list(tr.ticks())
        assert [t for t, _ in ticks] == [0, 10, 20]
        assert sum(len(evs) for _, evs in ticks) == 8

    def test_rejects_time_going_backwards(self):
        b = TraceBuilder("t", 0, 1, 64, 32)
        b.open(10, "a", 0)
        b.data(0, "a", 0)
        with pytest.raises(ValueError, match="backwards"):
            b.build()

    def test_rejects_data_before_open(self):
        b = TraceBuilder("t", 0, 1, 64, 32)
        b.data(0, "a", 0)
        b.sessions["a"] = {"stream": 0, "mode": "raw"}
        with pytest.raises(ValueError, match="unopened"):
            b.build()

    def test_rejects_reopen_and_post_close(self):
        b = TraceBuilder("t", 0, 1, 64, 32)
        b.open(0, "a", 0)
        b.open(10, "a", 0)
        with pytest.raises(ValueError, match="reopened"):
            b.build()
        b2 = TraceBuilder("t", 0, 1, 64, 32)
        b2.open(0, "a", 0)
        b2.close(0, "a")
        b2.data(10, "a", 0)
        with pytest.raises(ValueError, match="already closed"):
            b2.build()

    def test_rejects_nonincreasing_window_ref(self):
        b = TraceBuilder("t", 0, 1, 64, 32)
        b.open(0, "a", 0)
        b.data(0, "a", 1)
        b.data(10, "a", 1)
        with pytest.raises(ValueError, match="not increasing"):
            b.build()

    def test_rejects_bad_schema_header(self):
        with pytest.raises(ValueError, match="schema"):
            Trace.from_jsonl('{"schema":"nope/v9"}\n')


# ------------------------------------------------------------ scenario zoo


class TestScenarioZoo:
    def test_every_scenario_synthesizes_valid(self):
        for name in SCENARIOS:
            tr = synthesize(name, seed=scenario_seed(name))
            tr.validate()  # no-throw
            assert tr.counts()["sessions"] >= 1

    def test_same_seed_same_digest(self):
        for name in ("flash_crowd", "dropout_churn", "slot_churn"):
            a = synthesize(name, seed=3).digest()
            b = synthesize(name, seed=3).digest()
            c = synthesize(name, seed=4).digest()
            assert a == b
            assert a != c

    def test_mixed_fleet_carries_both_modes(self):
        tr = synthesize("mixed_fleet", seed=0)
        modes = {m["mode"] for m in tr.sessions.values()}
        assert modes == {"raw", "pieces"}

    def test_dropout_churn_reconnects_share_stream_rows(self):
        tr = synthesize("dropout_churn", seed=scenario_seed("dropout_churn"))
        rows = [m["stream"] for m in tr.sessions.values()]
        assert len(rows) > len(set(rows))  # at least one row resumed

    def test_slot_churn_oversubscribes_its_slot_table(self):
        sc = SCENARIOS["slot_churn"]
        tr = synthesize("slot_churn", seed=scenario_seed("slot_churn"))
        assert tr.counts()["sessions"] > sc.server_kw["max_sessions"]
        assert sc.server_kw["evict_idle"]

    def test_synthesize_requires_explicit_seed(self):
        with pytest.raises(TypeError):
            synthesize("flash_crowd")  # seed is keyword-only on purpose

    def test_row_seeds_are_order_invariant(self):
        # the bench harness seeds every scenario row explicitly via
        # scenario_seed(name, base); synthesizing in any order -- or
        # skipping rows -- must not perturb any row's trace
        names = ["bursty", "flash_crowd", "slot_churn"]
        forward = {n: synthesize(n, seed=scenario_seed(n, 0)).digest()
                   for n in names}
        backward = {n: synthesize(n, seed=scenario_seed(n, 0)).digest()
                    for n in reversed(names)}
        alone = {"flash_crowd": synthesize(
            "flash_crowd", seed=scenario_seed("flash_crowd", 0)).digest()}
        assert forward == backward
        assert forward["flash_crowd"] == alone["flash_crowd"]


# ---------------------------------------------------------- legacy shims


def _reference_arrival_schedule(pattern, n_sessions, n_windows, rng):
    """Frozen copy of ``launch.stream._arrival_schedule`` as of its
    retirement (PR 10) -- the shim-equivalence oracle.  Do not edit."""
    cursors = [0] * n_sessions
    if pattern == "roundrobin":
        while any(c < n_windows for c in cursors):
            tick = [(s, cursors[s]) for s in range(n_sessions)
                    if cursors[s] < n_windows]
            for s, _ in tick:
                cursors[s] += 1
            yield tick
    elif pattern == "random":
        while any(c < n_windows for c in cursors):
            live = [s for s in range(n_sessions) if cursors[s] < n_windows]
            pick = [s for s in live if rng.random() < 0.6] or live[:1]
            tick = [(s, cursors[s]) for s in pick]
            for s, _ in tick:
                cursors[s] += 1
            yield tick
    elif pattern == "bursty":
        s = 0
        while any(c < n_windows for c in cursors):
            live = [i for i in range(n_sessions) if cursors[i] < n_windows]
            s = live[s % len(live)]
            burst = min(int(rng.integers(1, 4)), n_windows - cursors[s])
            for _ in range(burst):
                yield [(s, cursors[s])]
                cursors[s] += 1
            s += 1
    else:
        raise ValueError(pattern)


class TestLegacyShims:
    @pytest.mark.parametrize("pattern", ["roundrobin", "random", "bursty"])
    @pytest.mark.parametrize("seed", [0, 7])
    def test_shim_reproduces_retired_schedule(self, pattern, seed):
        sessions, length, window = 6, 384, 48
        n_windows = -(-length // window)
        rng = np.random.default_rng(seed)
        want = [list(t) for t in _reference_arrival_schedule(
            pattern, sessions, n_windows, rng)]
        wl = Workload.from_pattern(pattern, sessions=sessions, length=length,
                                   window=window, seed=seed, _warn=False)
        got = wl.trace().schedule()
        assert got == want

    def test_generator_port_matches_reference_directly(self):
        for seed in (0, 5):
            want = list(_reference_arrival_schedule(
                "bursty", 4, 6, np.random.default_rng(seed)))
            got = list(legacy_arrival_schedule(
                "bursty", 4, 6, np.random.default_rng(seed)))
            assert got == want

    def test_from_pattern_warns_deprecation(self):
        with pytest.warns(DeprecationWarning, match="arrival-pattern"):
            Workload.from_pattern("bursty", sessions=2, length=64,
                                  window=32, seed=0)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            Workload.from_pattern("bursty", sessions=2, length=64,
                                  window=32, seed=0, _warn=False)


# ------------------------------------------------------------------- SLOs


class TestSLOs:
    def test_parse_good(self):
        assert parse_slo("p99_symbol_ms=50") == ("p99_symbol_ms", 50.0)
        assert parse_slo_specs(["evict_rate=0.5", "evict_rate=0.25"]) == {
            "evict_rate": 0.25}

    def test_parse_rejects_unknown_key_and_bad_shape(self):
        with pytest.raises(ValueError, match="unknown SLO"):
            parse_slo("p42_symbol_ms=1")
        with pytest.raises(ValueError, match="key=limit"):
            parse_slo("p99_symbol_ms")
        with pytest.raises(ValueError):
            parse_slo("p99_symbol_ms=fast")

    def test_check_slos_flags_only_exceeded(self):
        measured = {"p99_symbol_ms": 80.0, "max_queue_depth": 3.0,
                    "evict_rate": 0.0}
        v = check_slos(measured, {"p99_symbol_ms": 50.0,
                                  "max_queue_depth": 64.0})
        assert [x.key for x in v] == ["p99_symbol_ms"]
        assert "p99_symbol_ms" in str(v[0])

    def test_check_slos_missing_measurement_violates(self):
        v = check_slos({}, {"p99_symbol_ms": 50.0})
        assert len(v) == 1 and np.isnan(v[0].measured)

    def test_known_slos_cover_scenario_defaults(self):
        for sc in SCENARIOS.values():
            assert set(sc.slos) <= set(KNOWN_SLOS)


# --------------------------------------------------- replay determinism


def _small_cfg():
    from repro.core.symed import SymEDConfig

    return SymEDConfig(tol=0.5, alpha=0.02, scl=1.0, k_min=3, k_max=8,
                       len_max=32, n_max=64, lloyd_iters=5)


class TestReplayDeterminism:
    def test_two_runs_bitwise_identical(self):
        from repro.workload.replay import replay_trace

        tr = synthesize("mixed_fleet", seed=scenario_seed("mixed_fleet"),
                        sessions=4, length=64, window=32)
        kw = {"max_sessions": 4, "pretrace": True}
        a = replay_trace(tr, cfg=_small_cfg(), server_kw=kw, verify=True)
        b = replay_trace(tr, cfg=_small_cfg(), server_kw=kw, verify=True)
        assert a.delta_sha256 == b.delta_sha256
        assert a.counters == b.counters  # every obs counter total
        assert a.fingerprint() == b.fingerprint()
        assert a.verified == len(tr.sessions)

    def test_eviction_churn_deterministic(self):
        from repro.workload.replay import replay_trace

        # 5 sessions per wave + the background stream oversubscribe the
        # scenario's 4-slot table, so LRU eviction must fire
        wl = Workload("slot_churn", seed=scenario_seed("slot_churn"),
                      sessions=5, length=64, window=32)
        runs = [replay_trace(wl.trace(), cfg=_small_cfg(),
                             server_kw=wl.server_kw()) for _ in range(2)]
        assert runs[0].counters["evicted"] > 0  # scenario does its job
        assert runs[0].fingerprint() == runs[1].fingerprint()
        assert runs[0].counters == runs[1].counters

    def test_transport_serve_failure_raises_in_caller(self, monkeypatch):
        from repro.launch.stream import StreamServer
        from repro.workload.replay import replay_trace

        def boom(self, *a, **kw):
            raise ValueError("boom in the device step")

        monkeypatch.setattr(StreamServer, "ingest_many", boom)
        tr = synthesize("flash_crowd", seed=scenario_seed("flash_crowd"),
                        sessions=2, length=64, window=32)
        with pytest.raises(RuntimeError, match="serve loop failed"):
            replay_trace(tr, cfg=_small_cfg(), server_kw={"max_sessions": 2},
                         transport=True, close_timeout=30)

    @pytest.mark.slow
    def test_transport_matches_inprocess(self):
        from repro.workload.replay import LOOSE_COUNTER_KEYS, replay_trace

        tr = synthesize("mixed_fleet", seed=scenario_seed("mixed_fleet"),
                        sessions=4, length=64, window=32)
        kw = {"max_sessions": 4, "pretrace": True}
        inproc = replay_trace(tr, cfg=_small_cfg(), server_kw=kw)
        wire = replay_trace(tr, cfg=_small_cfg(), server_kw=kw,
                            transport=True, verify=True)
        assert wire.delta_sha256 == inproc.delta_sha256
        for k in LOOSE_COUNTER_KEYS:
            assert wire.counters[k] == inproc.counters[k], k

    @pytest.mark.slow
    def test_cli_devices_invariance(self, tmp_path):
        """--devices 1 vs 4: identical delta bytes + counter totals."""
        outs = {}
        for dev in (1, 4):
            out = tmp_path / f"bench_d{dev}.json"
            proc = subprocess.run(
                [sys.executable, "-m", "repro.workload",
                 "--scenario", "flash_crowd", "--sessions", "8",
                 "--length", "96", "--window", "32",
                 "--devices", str(dev), "--out", str(out)],
                capture_output=True, text=True, env=SUBENV, cwd=REPO,
                timeout=600)
            assert proc.returncode == 0, proc.stderr[-2000:]
            outs[dev] = json.load(open(out))["rows"][0]
        for key in ("delta_sha256", "trace_digest", "opened", "closed",
                    "evicted", "points_in", "symbols_out",
                    "max_queue_depth", "drains"):
            assert outs[1][key] == outs[4][key], key

    @pytest.mark.slow
    def test_cli_exit_codes(self, tmp_path):
        """Exit 0 when SLOs hold, 1 when violated; artifact records both."""
        base = [sys.executable, "-m", "repro.workload",
                "--scenario", "mixed_fleet", "--sessions", "2",
                "--length", "64", "--window", "32"]
        ok = subprocess.run(base, capture_output=True, text=True,
                            env=SUBENV, cwd=REPO, timeout=600)
        assert ok.returncode == 0, ok.stderr[-2000:]
        assert "violations=0" in ok.stdout
        out = tmp_path / "violated.json"
        bad = subprocess.run(
            base + ["--slo", "p99_symbol_ms=0.0001", "--out", str(out)],
            capture_output=True, text=True, env=SUBENV, cwd=REPO,
            timeout=600)
        assert bad.returncode == 1, (bad.returncode, bad.stderr[-2000:])
        assert "VIOLATION" in bad.stdout
        doc = json.load(open(out))
        assert doc["schema"] == "bench_transport/v1"
        assert doc["rows"][0]["violations"]

#!/usr/bin/env python3
"""One benchmark run of one cell of the SymED edge service on the chip.

    python3 benchmarks/chip/run.py --workload ucr_pieces.rate --seed 7 \
        --seconds 30 --trace 0

This process holds the chip and runs the served path as a user deploys it:
a ``TransportServer`` in front of a ``StreamServer`` (the donated table
steps with the Pallas Lloyd kernel).  A child process (``loadgen.py``,
JAX pinned to the host CPU) is the sensor fleet: it drives the server over
loopback TCP on an open-loop schedule and records what it sent and got.

Everything of one cell is found by name: the workload entry in
``BENCHMARK.json`` names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``); every metric of the cell is read by
``metrics/<name>.py`` (per-layer) or by this file (end-to-end).

The run: set-up (server built, sensor frames cut, warm-up traffic until
every program the window runs has compiled, and every sensor's series
filled up to its seeded starting window) -> the measured window
(``--seconds``) -> the open series finish and every CLOSED frame is
awaited -> the peak device memory is read, the server is freed, and the
sampled sessions are compared with the plain reference (``checks.py``).
The last stdout line is the result JSON; the numbers compared, each with
its limit, are the last stderr lines and the result's last key.

``--trace 1`` runs the JAX profiler over the window and reports the
per-layer metrics instead of the end-to-end ones.  ``--rehearse`` runs the
same path on the CPU at tiny sizes (kernels interpreted) and reports no
metric.  Without ``--rehearse`` a run that finds no TPU exits non-zero
before printing anything.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys
import threading
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

# tiny sizes of the CPU rehearsal (widths cut too: interpret mode is slow)
REHEARSAL = {"slots": 8, "concurrent_sessions": 4, "series_points": 512,
             "window_points": 128, "n_max": 128, "k_max": 16, "len_max": 128}
REHEARSAL_POINTS_PER_S = 512
SAMPLE_SESSIONS = 32   # finished sessions compared with the reference
TRACE_SECONDS = 12.0   # the traced run profiles the window's first seconds


def load_cell(name: str, rehearse: bool = False) -> dict:
    """The workload entry, its configuration, traffic and metric entries."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    if rehearse:
        cfg.update({k: v for k, v in REHEARSAL.items() if k in cfg})
        traffic["offered_points_per_s"] = REHEARSAL_POINTS_PER_S
    for_cell = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return {"cell": cell, "cfg": cfg, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if for_cell(m)],
            "per_layer": [m for m in bench["per_layer"] if for_cell(m)]}


def load_reader(metric: str):
    """``metrics/<metric>.py``, else the reader of its base name (the part
    before the first dot), which serves every split of one quantity."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Every program JAX lowers or compiles, stamped on the monotonic clock
    (``jax.monitoring`` events), so the window can count its own."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.stamps = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.stamps.append((time.monotonic(), event))

    def count(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.stamps if t0 <= t <= t1)


class TickCount:
    """Serve-loop ticks completed, counted around the transport's tick, so
    set-up can wait until the server has processed all it has read."""

    def __init__(self, transport):
        self.n = 0
        tick = transport._tick

        def counted(poll):
            tick(poll)
            self.n += 1

        transport._tick = counted

    def await_processed(self, transport, serving, nbytes: int) -> None:
        """Until the server has read ``nbytes`` and finished the tick that
        read the last of them (its replies are sent by then)."""
        deadline = time.monotonic() + 900.0
        while transport.frame_bytes < nbytes:
            if time.monotonic() > deadline or serving.error is not None:
                raise RuntimeError(f"the server read {transport.frame_bytes}"
                                   f" of the fill's {nbytes} bytes")
            time.sleep(0.01)
        done = self.n
        while self.n <= done:
            if time.monotonic() > deadline or serving.error is not None:
                raise RuntimeError("the server never finished the fill")
            time.sleep(0.005)


def build_server(cfg: dict, seed: int, trace: bool):
    from repro.core.symed import SymEDConfig
    from repro.launch.stream import StreamServer
    from repro.launch.transport import ServeThread, TransportServer
    from repro.obs import Observability

    sym = SymEDConfig(tol=cfg["tol"], alpha=cfg["alpha"], scl=cfg["scl"],
                      k_min=cfg["k_min"], k_max=cfg["k_max"],
                      len_max=cfg["len_max"], n_max=cfg["n_max"],
                      lloyd_iters=cfg["lloyd_iters"])
    obs = Observability(trace_capacity=1 << 20, jax_annotate=trace)
    server = StreamServer(
        sym, max_sessions=cfg["slots"], window_cap=cfg["window_points"],
        digitize_every_k=cfg["digitize_every_k"], dtw_every=cfg["dtw_every"],
        use_kernel=cfg["use_kernel"], pretrace=False, seed=seed, obs=obs)
    transport = TransportServer(server, port=0)
    ticks = TickCount(transport)
    stop = threading.Event()
    serving = ServeThread(transport, stop=stop, poll=0.005)
    return server, transport, ticks, serving, stop


def warm_dtw_gathers(server, sessions: int) -> None:
    """The DTW monitor gathers its due sessions out of the table in one
    program per count of due sessions; compile every count the fleet can
    reach, so none compiles inside the window."""
    import jax
    import jax.numpy as jnp

    from repro.launch import stream

    def gather(n):
        out = stream._gather_slots(server._table,
                                   jnp.arange(n, dtype=jnp.int32))
        jax.block_until_ready(out)

    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        list(pool.map(gather, range(1, sessions + 1)))


def device_info(rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    stats = devs[0].memory_stats() if not rehearse else None
    if stats:
        info["memory_peak_bytes"] = max(
            int(d.memory_stats().get("peak_bytes_in_use", 0)) for d in devs)
    return info


def percentile(values, q):
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True,
                    help="draws the sessions that correct checks; the traffic "
                         "and its digitizer seeds come from the configuration")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; reports no metric")
    ap.add_argument("--offered", type=float, default=None,
                    help="offered points/s instead of the traffic file's "
                         "(the knee sweep)")
    args = ap.parse_args(argv)

    c = load_cell(args.workload, args.rehearse)
    cfg, traffic = c["cfg"], c["traffic"]
    if args.offered:
        traffic["offered_points_per_s"] = args.offered
    out_dir = ROOT / ".bench_out" / f"{args.workload}.{args.seed}"
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime's logs stay in the checkout too (default: /tmp)
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()
    if not args.rehearse and (dev[0].platform != "tpu"
                              or len(dev) < c["cell"]["chips"]):
        print(f"run.py: needs {c['cell']['chips']} TPU chip(s), JAX has "
              f"{len(dev)} {dev[0].platform} device(s)", file=sys.stderr)
        return 1
    compiles = CompileLog()

    server, transport, ticks, serving, stop = build_server(
        cfg, cfg["fleet_seed"], bool(args.trace))
    spec = {**traffic, "seconds": args.seconds,
            "mode": cfg["mode"], "port": transport.port,
            "sensors": cfg["concurrent_sessions"],
            "window_points": cfg["window_points"],
            "series_points": cfg["series_points"],
            "cfg": {k: cfg[k] for k in ("tol", "alpha", "len_max", "n_max",
                                        "fleet_seed")},
            "out": str(out_dir / "loadgen.json")}
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    with open(out_dir / "loadgen.err", "w") as err:
        child = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py"), json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err,
            text=True, env=env)
        try:
            rec = drive(child, server, transport, ticks, serving, stop,
                        compiles, cfg, args, out_dir)
        finally:
            if child.poll() is None:
                child.kill()
            child.wait()
            stop.set()
    if rec is None:
        tail = (out_dir / "loadgen.err").read_text()[-3000:]
        print(f"run.py: the load generator failed:\n{tail}", file=sys.stderr)
        return 1
    return report(rec, server, c, args)


def drive(child, server, transport, ticks, serving, stop, compiles, cfg,
          args, out_dir):
    """Walk the child through its phases; returns the run's record."""
    rec = {"setup_s": None, "window": None,
           "built_s": time.perf_counter() - T_START}
    trace_dir = out_dir / "trace"
    with serving.root_cause():
        for line in child.stdout:
            word = line.split()
            if not word:
                continue
            if word[0] == "FILL":
                ticks.await_processed(transport, serving, int(word[1]))
                child.stdin.write("FILLED\n")
                child.stdin.flush()
            elif word[0] == "WARM":
                t_warm = time.perf_counter()
                if cfg["dtw_every"]:
                    warm_dtw_gathers(server, cfg["concurrent_sessions"])
                print(f"run: set-up: server built {rec['built_s']} s, sensor "
                      f"frames cut {word[1]} s, warm-up traffic {word[2]} s, "
                      f"fill {word[3]} s, DTW gathers "
                      f"{time.perf_counter() - t_warm} s", file=sys.stderr)
                rec["retraces_warm"] = server._cache_entries()
                rec["setup_s"] = time.perf_counter() - T_START
                child.stdin.write("GO\n")
                child.stdin.flush()
            elif word[0] == "WINDOW":
                w0, w1 = float(word[1]), float(word[2])
                rec["window"] = (w0, w1)
                _sleep_until(w0)
                if args.trace:
                    import jax

                    jax.profiler.start_trace(str(trace_dir))
                    rec["trace_t0"] = time.monotonic()
                _sleep_until(min(w1, w0 + TRACE_SECONDS)
                             if args.trace else w1)
                if args.trace:
                    rec["trace_t1"] = time.monotonic()
                    jax.profiler.stop_trace()
            elif word[0] == "DONE":
                break
    if child.wait() != 0 or rec["window"] is None:
        return None
    stop.set()
    serving.join(timeout=60.0)
    rec["loadgen"] = json.loads((out_dir / "loadgen.json").read_text())
    w0, w1 = rec["window"]
    rec["compiles_in_window"] = compiles.count(w0, w1)
    rec["retraces"] = server._cache_entries() - rec["retraces_warm"]
    rec["trace_dir"] = trace_dir
    return rec


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def report(rec, server, c, args) -> int:
    import numpy as np

    import checks
    import loadgen

    cfg, traffic = c["cfg"], c["traffic"]
    gen = rec["loadgen"]
    w0, w1 = rec["window"]
    device = device_info(args.rehearse)
    ctx = None
    if args.trace:
        import trace_reduce

        ctx = trace_reduce.Context(
            trace_dir=rec["trace_dir"], window=(rec["trace_t0"],
                                                rec["trace_t1"]),
            spans=server.obs.tracer.events(), cfg=cfg, loadgen=gen,
            device_kind=device["kind"])
    steps = [(t0 / 1e9, d / 1e9, (a or {}).get("sessions"))
             for n, ph, t0, d, a in server.obs.tracer.events()
             if n.startswith("stream.harvest")]
    (rec["trace_dir"].parent / "steps.json").write_text(json.dumps(steps))
    server._table = None  # free the slot table before the reference runs

    # ---- correctness: the served sample against the plain reference -----
    plan_spec = {**traffic, "seconds": args.seconds,
                 "sensors": cfg["concurrent_sessions"],
                 "window_points": cfg["window_points"],
                 "series_points": cfg["series_points"], "cfg": cfg}
    if args.offered:
        plan_spec["offered_points_per_s"] = args.offered
    rows = loadgen.plan(plan_spec)[0]
    sessions = gen["sessions"]
    failed = sum(1 for s in sessions if not s["closed"] or s["error"]
                 or s["evicted"])
    picked = checks.sample(sessions, SAMPLE_SESSIONS, args.seed)
    t_ref = time.perf_counter()
    want = checks.reference_streams(
        cfg, rows, picked, fused=checks.fused_compressor(cfg, args.rehearse))
    served = [{"symbols": np.asarray(s["labels"]),
               "endpoints": np.asarray(s["endpoints"], np.float32)}
              for s in picked]
    verdict = checks.compare(served, want, failed)
    ref_s = time.perf_counter() - t_ref

    lat = np.asarray(gen["latencies_s"], np.float64)
    secs = w1 - w0
    print(f"run: setup_s={rec['setup_s']} window_s={secs} "
          f"symbols={gen['symbols_in_window']} sessions={len(sessions)} "
          f"completed={gen['completed']} drained_s={gen['drained_s']} "
          f"period_s={gen['period_s']} reference_s={ref_s} "
          f"compiles_in_window={rec['compiles_in_window']} "
          f"retraces_after_warmup={rec['retraces']} "
          f"unmatched_endpoints={gen['unmatched_endpoints']}", file=sys.stderr)
    print(f"run: symbols due {gen['symbols_due_in_window']} and received "
          f"{gen['symbols_in_window']} in the window; backlog of due symbols "
          f"over it {gen['symbol_backlog']}", file=sys.stderr)
    if len(lat):
        print(f"run: latency p50_ms={1e3 * percentile(lat, 50)} "
              f"p95_ms={1e3 * percentile(lat, 95)} symbols_per_s="
              f"{gen['symbols_in_window'] / secs}", file=sys.stderr)
    if len(gen["lag_s"]):
        print(f"run: generator lag p50_ms={1e3 * percentile(gen['lag_s'], 50)}"
              f" p95_ms={1e3 * percentile(gen['lag_s'], 95)} "
              f"max_ms={1e3 * max(gen['lag_s'])}", file=sys.stderr)
    out = {"correct": verdict["correct"] and rec["compiles_in_window"] == 0,
           "attempted": len(sessions), "failed": failed}
    metrics = {}
    if args.rehearse:
        if args.trace:
            for m in c["per_layer"]:
                print(f"rehearsal {m['name']}: "
                      f"{load_reader(m['name'])(ctx)} (not a device number)",
                      file=sys.stderr)
    elif args.trace:
        busy_s, window_s, breakdown = ctx.device_summary()
        device["busy_s"] = busy_s
        device["window_s"] = window_s
        for m in c["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = breakdown
    else:
        e2e = {
            "symbols_per_s": gen["symbols_in_window"] / secs,
            "symbol_latency_p50_ms": (1e3 * percentile(lat, 50)
                                      if len(lat) else None),
            "symbol_latency_p95_ms": (1e3 * percentile(lat, 95)
                                      if len(lat) else None),
            "setup_s": rec["setup_s"],
        }
        for m in c["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    checks_out = {**verdict["numbers"],
                  "compiles_in_window": {"value": rec["compiles_in_window"],
                                         "limit": 0}}
    out["checks"] = checks_out
    print(f"run: {verdict['symbols_compared']} symbols of {len(picked)} "
          f"sessions compared", file=sys.stderr)
    for k, v in checks_out.items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr)
    if args.trace:
        shutil.rmtree(rec["trace_dir"], ignore_errors=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Self-check of the trace reduction and the per-layer readers, on the CPU.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/selfcheck.py

Reduces the small recorded trace in ``fixture/`` (one traced table step of
a 16-slot table on a TPU v5e, ``record_fixture.py``) and checks:

* the device busy time, step count and Lloyd kernel calls against a second,
  plain reduction of the same events (sort and merge with numpy);
* every reader's value against ``fixture/expected.json``;
* shares inside [0, 100].

Exits non-zero on any difference.  ``--write`` rewrites expected.json.
"""
from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import run  # noqa: E402
import trace_reduce  # noqa: E402

FIX = HERE / "fixture"
READERS = ("step_device_ms.lat", "kmeans_roofline.tput",
           "device_idle_share.lat", "host_path_ms.lat", "gen_lag_p95_ms.lat")


def plain_reduction(path: pathlib.Path):
    """Busy seconds, step runs and kernel calls, by another route."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    (plane,) = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    lines = {ln.name: ln for ln in plane.lines}
    ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
          for e in lines["XLA Ops"].events]
    iv = np.asarray(sorted((s, t) for s, t, _ in ev), np.float64)
    busy, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, t in iv[1:]:
        if s > cur_e:
            busy += cur_e - cur_s
            cur_s, cur_e = s, t
        else:
            cur_e = max(cur_e, t)
    busy += cur_e - cur_s
    steps = sum(1 for e in lines["XLA Modules"].events
                if "table_step" in e.name)
    kernels = sum(1 for _, _, n in ev if "kmeans_assign_pallas" in n
                  and "custom-call" in n)
    return busy / 1e9, steps, kernels


def main() -> int:
    ctx_json = json.loads((FIX / "context.json").read_text())
    ctx = trace_reduce.Context(
        trace_dir=FIX, window=tuple(ctx_json["window"]),
        spans=[tuple(s) for s in ctx_json["spans"]], cfg=ctx_json["cfg"],
        loadgen={"lag_s": ctx_json["lag_s"]},
        device_kind=ctx_json["device_kind"])
    busy, steps, kernels = plain_reduction(FIX / "trace.xplane.pb")
    failures = []
    got_busy = ctx.devices[0]["busy_s"]
    if abs(got_busy - busy) > 1e-9:
        failures.append(f"busy_s {got_busy} != plain {busy}")
    if len(ctx.devices[0]["steps"]) != steps:
        failures.append(f"steps {ctx.devices[0]['steps']} != plain {steps}")
    if len(ctx.kernel_calls()) != kernels:
        failures.append(f"kernel calls {len(ctx.kernel_calls())} != plain "
                        f"{kernels}")
    values = {m: run.load_reader(m)(ctx) for m in READERS}
    for m, v in values.items():
        print(f"{m} = {v}")
        if v is None:
            failures.append(f"{m} read nothing")
        elif m.split(".")[0].endswith(("roofline", "share")) \
                and not 0.0 <= v <= 100.0:
            failures.append(f"{m} = {v} outside [0, 100]")
    exp_path = FIX / "expected.json"
    if "--write" in sys.argv:
        exp_path.write_text(json.dumps(values, indent=1) + "\n")
    else:
        want = json.loads(exp_path.read_text())
        for m, v in want.items():
            if v is None or values[m] is None \
                    or abs(values[m] - v) > 1e-9 * max(1.0, abs(v)):
                failures.append(f"{m} = {values[m]}, expected {v}")
    for f in failures:
        print("selfcheck: " + f, file=sys.stderr)
    print("selfcheck: " + ("FAILED" if failures else "ok"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

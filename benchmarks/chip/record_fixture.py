#!/usr/bin/env python3
"""Record the small trace that ``selfcheck.py`` reduces: one traced step of
a 16-slot table on the chip, with the program's host spans.

    python3 benchmarks/chip/record_fixture.py

Writes ``fixture/trace.xplane.pb`` and ``fixture/context.json`` (the host
spans, the traced window on the monotonic clock, the table's sizes and
the device kind).  Run it on a TPU; the files are committed.
"""
from __future__ import annotations

import glob
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import fleet  # noqa: E402


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_fixture.py: needs a TPU", file=sys.stderr)
        return 1
    from repro.launch.stream import StreamServer
    from repro.core.symed import SymEDConfig
    from repro.obs import Observability

    cfg = json.loads((HERE / "configs" / "ucr_pieces.json").read_text())
    cfg["slots"] = 16
    sym = SymEDConfig(tol=cfg["tol"], alpha=cfg["alpha"], scl=cfg["scl"],
                      k_min=cfg["k_min"], k_max=cfg["k_max"],
                      len_max=cfg["len_max"], n_max=cfg["n_max"],
                      lloyd_iters=cfg["lloyd_iters"])
    server = StreamServer(sym, max_sessions=16, window_cap=256,
                          digitize_every_k=1, use_kernel=True,
                          obs=Observability(jax_annotate=True))
    rows, _ = fleet.make_rows(8, 512, 0, cfg)
    sids = [f"s{i}" for i in range(8)]
    for s in sids:
        server.open(s)
    server.ingest_many({s: r[:256] for s, r in zip(sids, rows)})
    tmp = tempfile.mkdtemp()
    t0 = time.monotonic()
    jax.profiler.start_trace(tmp)
    server.ingest_many({s: r[256:] for s, r in zip(sids, rows)})
    jax.profiler.stop_trace()
    t1 = time.monotonic()
    out = HERE / "fixture"
    out.mkdir(exist_ok=True)
    shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0],
                out / "trace.xplane.pb")
    shutil.rmtree(tmp)
    spans = [list(e) for e in server.obs.tracer.events() if e[1] == "X"]
    dev = jax.devices()[0]
    (out / "context.json").write_text(json.dumps({
        "window": [t0, t1], "spans": spans, "cfg": cfg,
        "device_kind": dev.device_kind, "lag_s": [0.001, 0.002, 0.004]}))
    print(f"recorded {os.path.getsize(out / 'trace.xplane.pb')} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())

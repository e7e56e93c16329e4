#!/usr/bin/env python3
"""The lower-precision control: the plain reference put in the served
path's place, computed in bfloat16 (the precision below the float32 the
configuration states), and judged by the same comparison as a run.

    python3 benchmarks/chip/control.py --workload ucr_pieces.rate \
        --seeds 1 2 3 --seconds 30

Uses the sessions a run of that cell, seed and length would finish, each
cut where the run's would be, and the same sample rule.  In pieces mode the sensor compresses in float32 and the
control digitizes in bfloat16, as the chip would; in raw mode the control
compresses and digitizes in bfloat16.  Every seed must come out not
correct: its numbers are the upper readings of the limits in ``checks``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

import checks  # noqa: E402
import loadgen  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

BF16 = ml_dtypes.bfloat16


def control(workload: str, seed: int, seconds: float) -> dict:
    """The comparison's numbers for the control, on the sessions a run of
    that cell, seed and window would finish: each with the points it would
    have sent when it closes (the fill's windows, those due inside the
    window, then the close), sampled by the run's rule."""
    c = run.load_cell(workload)
    cfg, traffic = c["cfg"], c["traffic"]
    spec = {**traffic, "seconds": seconds,
            "sensors": cfg["concurrent_sessions"],
            "window_points": cfg["window_points"],
            "series_points": cfg["series_points"], "cfg": cfg}
    rows, _, sessions, _ = loadgen.plan(spec)
    fused = checks.fused_compressor(cfg, False)
    planned = [{"row": s.row, "seed": s.seed,
                "points_sent": s.windows(seconds) * cfg["window_points"],
                "closed": True, "error": None, "evicted": False}
               for s in sessions]
    # the run picks its longest session by the pieces the server reports
    for length in {s["points_sent"] for s in planned}:
        group = [s for s in planned if s["points_sent"] == length]
        comp = reference.compress(
            np.stack([rows[s["row"]][:length] for s in group]),
            tol=cfg["tol"], len_max=cfg["len_max"], alpha=cfg["alpha"],
            fused=fused)
        for s, n in zip(group, comp["emit"].sum(1) + comp["tail_emit"]):
            s["n_pieces"] = int(n)
    picked = checks.sample(planned, run.SAMPLE_SESSIONS, seed)
    want = checks.reference_streams(cfg, rows, picked, fused=fused)
    raw = cfg["mode"] == "raw"
    got = checks.reference_streams(
        cfg, rows, picked, fused=fused, dtype=BF16 if raw else np.float32,
        digitize_dtype=BF16)
    lengths = sorted(s["points_sent"] for s in picked)
    return {**checks.compare(got, want, 0), "points_sent": lengths}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    for seed in args.seeds:
        v = control(args.workload, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": v["correct"],
                          "symbols_compared": v["symbols_compared"],
                          "points_sent": v["points_sent"],
                          "numbers": v["numbers"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Knee sweep of one cell: the same run at a ladder of offered rates.

    python3 benchmarks/chip/sweep.py --workload ucr_pieces.rate \
        --rates 1200 1600 2000 2400 --seconds 51 --seed 11

Each rate is one ``run.py`` process (one process holds the chip at a
time).  The backlog is the count of due symbols (their piece's completing
window sent) less the symbols received, each second of the window.  A
table step of the filled fleet takes seconds, so the backlog is a
sawtooth that a sustained load keeps emptying: a rate is sustained when
the backlog's least value over the window's last third exceeds its least
value over the first third by no more than ``GROWTH`` of the symbols due
in the window.  The knee is the highest rate at which it and every lower
rate of the ladder are sustained.  Prints one line per rate (offered
points/s, symbols/s, latency p50/p95, symbols due and received, the
backlog, the rise of its floor, the verdict, ``correct``), then
``KNEE <rate>`` or ``KNEE none``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent
GROWTH = 0.05


def verdict(backlog, due: int):
    """(rise of the backlog's floor, sustained) of one window."""
    third = max(len(backlog) // 3, 1)
    rise = min(backlog[-third:]) - min(backlog[:third])
    return rise, rise <= GROWTH * due


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    knee, ok = None, True
    for i, rate in enumerate(sorted(args.rates)):
        proc = subprocess.run(
            [sys.executable, str(HERE / "run.py"), "--workload",
             args.workload, "--seed", str(args.seed + i), "--seconds",
             str(args.seconds), "--offered", str(rate)],
            capture_output=True, text=True)
        runs = [ln for ln in proc.stderr.splitlines()
                if ln.startswith(("run:", "check"))]
        last = proc.stdout.strip().splitlines()[-1:] or ["{}"]
        res = json.loads(last[0]) if last[0].startswith("{") else {}
        out = re.search(r"symbols due (\d+) and received (\d+) in the "
                        r"window; backlog of due symbols over it (\[.*\])",
                        proc.stderr)
        lat = re.search(r"latency p50_ms=(\S+) p95_ms=(\S+) "
                        r"symbols_per_s=(\S+)", proc.stderr)
        rise, sustained = (verdict(json.loads(out.group(3)),
                                   int(out.group(1)))
                           if out and not proc.returncode else (None, False))
        ok = ok and sustained
        if ok:
            knee = rate
        print(f"sweep {args.workload} offered={rate} rc={proc.returncode} "
              f"symbols_per_s={lat and lat.group(3)} "
              f"p50_ms={lat and lat.group(1)} p95_ms={lat and lat.group(2)} "
              f"due={out and out.group(1)} received={out and out.group(2)} "
              f"backlog={out and out.group(3)} rise={rise} "
              f"verdict={'sustained' if sustained else 'grows'} "
              f"correct={res.get('correct')}", flush=True)
        for ln in runs:
            print("   " + ln, flush=True)
        if proc.returncode:
            print(proc.stderr[-3000:], flush=True)
    print(f"KNEE {knee if knee is not None else 'none'}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

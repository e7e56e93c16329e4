"""Shared argparse surface for the launch CLIs.

``repro.launch.stream``, ``repro.launch.transport`` and
``repro.launch.fleet`` grew the same flags three times -- device forcing,
SymED knobs, metrics/trace export, slot-table shape -- with drifting
defaults and validation.  This module is the single place each group is
declared and validated, so the three CLIs (and ``repro.workload``) accept
and reject identically.

Import safety: this module must stay importable *before* jax -- the
``__main__`` blocks call :func:`prescan_host_devices` to pin the forced
host device count, and jax locks the device count on first init.  Nothing
here may import jax (directly or transitively).
"""
from __future__ import annotations

import argparse
import os
import sys

__all__ = [
    "prescan_host_devices",
    "add_devices_arg",
    "add_symed_args",
    "add_metrics_args",
    "add_slot_table_args",
    "validate_shared_args",
]


def prescan_host_devices(argv=None, default: str = "1") -> None:
    """Set ``XLA_FLAGS`` from a raw ``--devices`` scan, before jax imports.

    jax locks the host device count on first init, so argparse is too late:
    the ``__main__`` blocks call this on ``sys.argv`` before importing
    anything that pulls in jax.  A malformed value is left for argparse to
    reject with a proper message.

    The flag only forces devices on the *CPU* platform.  On an accelerator
    the device count is the hardware's; the CLIs then build their mesh from
    exactly ``--devices`` of those devices (``launch.fleet.fleet_data_mesh``),
    and asking for more than exist is an error.
    """
    argv = sys.argv if argv is None else argv
    n = default
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif a.startswith("--devices="):
            n = a.split("=", 1)[1]
    try:
        count = int(n)
    except ValueError:
        return
    if count > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={count} "
            + os.environ.get("XLA_FLAGS", "")
        )


def add_devices_arg(ap: argparse.ArgumentParser, *, default: int = 1,
                    help: str = "devices in the data mesh (>1 shards over "
                                "it); on the CPU platform, also the forced "
                                "host device count") -> None:
    ap.add_argument("--devices", type=int, default=default, help=help)


def add_symed_args(ap: argparse.ArgumentParser, *, seed: bool = True) -> None:
    """The compressor/digitizer knobs every driver threads into SymEDConfig."""
    ap.add_argument("--tol", type=float, default=0.5,
                    help="compression tolerance (paper's tol)")
    ap.add_argument("--alpha", type=float, default=0.01,
                    help="digitizer EWMA smoothing in (0, 1]")
    if seed:
        ap.add_argument("--seed", type=int, default=0,
                        help="base seed: synthetic data + per-session "
                             "digitizer keys")


def add_metrics_args(ap: argparse.ArgumentParser) -> None:
    """Flight-recorder export: Prometheus endpoint + Perfetto span trace."""
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics (+ /metrics.json, "
                         "/trace) on this port for the run's duration")
    ap.add_argument("--metrics-linger", type=float, default=0.0,
                    help="keep the metrics endpoint up this many seconds "
                         "after the run finishes (scrape window)")
    ap.add_argument("--trace-out", default=None,
                    help="write the span ring as Chrome trace-event JSON "
                         "(load at ui.perfetto.dev)")


def add_slot_table_args(ap: argparse.ArgumentParser, *,
                        max_slots: int = 4) -> None:
    """The resident ``StreamServer`` table shape (stream + transport serve)."""
    ap.add_argument("--max-slots", type=int, default=max_slots,
                    help="resident slot-table capacity")
    ap.add_argument("--min-slots", type=int, default=None,
                    help="autoscale floor (default: --devices)")
    ap.add_argument("--autoscale", action="store_true",
                    help="grow/shrink the slot table between steps "
                         "(power-of-two ladder from --min-slots)")
    ap.add_argument("--evict", action="store_true",
                    help="LRU-evict when sessions exceed slots")
    ap.add_argument("--digitize-every", type=int, default=1,
                    help="digitize cadence in ingest windows")
    ap.add_argument("--shrink-patience", type=int, default=3,
                    help="consecutive low-occupancy ticks before the table "
                         "walks down the ladder (1: shrink immediately)")
    ap.add_argument("--pretrace", action="store_true",
                    help="warm the jit cache for every ladder capacity at "
                         "server init (no tracing during serving)")


def validate_shared_args(ap: argparse.ArgumentParser, args) -> None:
    """Fail fast (exit 2 via ``ap.error``) before any jax work.

    Checks every shared flag the namespace actually carries (``getattr``
    guards), so one validator serves parsers that mounted different
    subsets.  Messages are part of the CLI contract -- subprocess tests
    pin them -- so change them deliberately.
    """
    def has(name):
        return getattr(args, name, None) is not None

    if has("streams") and args.streams < 1:
        ap.error(f"--streams must be >= 1, got {args.streams}")
    if has("sessions") and args.sessions < 1:
        ap.error(f"--sessions must be >= 1, got {args.sessions}")
    if has("length") and args.length < 2:
        ap.error(f"--length must be >= 2, got {args.length}")
    if has("window"):
        if args.window < 1:
            ap.error(f"--window must be >= 1, got {args.window}")
        if has("length") and args.window > args.length:
            ap.error(f"--window {args.window} exceeds --length {args.length}")
    if has("digitize_every") and args.digitize_every < 0:
        ap.error(f"--digitize-every must be >= 0, got {args.digitize_every}")
    if has("tol") and args.tol <= 0:
        ap.error(f"--tol must be > 0, got {args.tol}")
    if has("alpha") and not 0 < args.alpha <= 1:
        ap.error(f"--alpha must be in (0, 1], got {args.alpha}")
    if has("devices") and args.devices < 1:
        ap.error(f"--devices must be >= 1, got {args.devices}")
    if has("max_slots"):
        if args.max_slots < 1:
            ap.error(f"--max-slots must be >= 1, got {args.max_slots}")
        if has("devices") and args.max_slots % args.devices:
            ap.error(f"--max-slots {args.max_slots} must divide over "
                     f"--devices {args.devices}")
    if has("min_slots"):
        if has("max_slots") and not 1 <= args.min_slots <= args.max_slots:
            ap.error(f"--min-slots {args.min_slots} must be in "
                     f"[1, --max-slots {args.max_slots}]")
        if has("devices") and args.min_slots % args.devices:
            ap.error(f"--min-slots {args.min_slots} must divide over "
                     f"--devices {args.devices}")
    if has("shrink_patience") and args.shrink_patience < 1:
        ap.error(f"--shrink-patience must be >= 1, got {args.shrink_patience}")
    if has("metrics_port") and not 0 <= args.metrics_port <= 65535:
        ap.error(f"--metrics-port must be in [0, 65535], got "
                 f"{args.metrics_port}")
    if has("metrics_linger") and args.metrics_linger < 0:
        ap.error(f"--metrics-linger must be >= 0, got {args.metrics_linger}")

"""Sharded SymED fleet runtime: distributed senders -> edge receivers at scale.

This is the runtime the ``repro.core.symed`` docstring promises: a slab of
``(n_streams, T)`` sensor streams is sharded over one or more mesh axes with
``shard_map``; every device owns a sub-slab of sender+receiver pairs and runs
``symed_batch`` (or the streaming-receiver path) locally; fleet-level
telemetry (wire bytes, pieces, compression rate) is aggregated with on-mesh
``psum`` reductions so every shard returns the same replicated totals.

Ingestion modes:

  * **whole-stream** (``chunk_len=None``): one vmapped ``symed_encode`` per
    shard -- maximum throughput when the slab fits;
  * **streaming receiver** (``chunk_len=C``): the stream is processed in
    ``C``-point windows through the resumable ``ReceiverState`` of
    ``repro.core.symed.symed_receive_chunk``.  What crosses each window
    boundary is O(n_max) per stream, independent of T: the O(1) sender
    ``CompressorState``, the padded wire buffers (endpoints + arrival steps),
    and the resumable ``DigitizerState``.  The digitize cadence
    ``digitize_every_k = k`` runs the receiver's k-means over the newly
    arrived pieces every ``k`` windows, so symbols stream out *online* while
    points are still arriving (the paper's 42ms/symbol deployment shape);
    ``k=0``/``None`` defers digitization to end-of-stream.  For every window
    split and cadence the end-of-stream outputs are bitwise-identical to the
    whole-stream path (tested in ``tests/test_streaming_receiver.py``).

Mesh layouts:

  * **single-pod** (``axis="data"``): flat 1-D sharding, e.g. the (16, 16)
    dry-run pod's ``data`` axis;
  * **multi-pod** (``axis=("pod", "data")``): streams shard over the flattened
    ``pod x data`` device grid and telemetry reduces *hierarchically* -- a
    ``psum`` over ``data`` (ICI, within-pod) first, then a ``psum`` over
    ``pod`` (DCN, across pods) -- the reduction tree a real multi-pod
    deployment would use.  Totals are invariant to the device layout: 1
    device, ``(8,)``, and ``(2, 4)`` produce identical ``pieces`` /
    ``wire_bytes`` / ``compression_rate`` (per-stream PRNG keys are split
    before sharding; tested via CLI subprocesses in ``tests/test_fleet.py``).

CLI (CPU dry-run; forces N host devices before jax initializes, mirroring
``repro.launch.dryrun``):

    PYTHONPATH=src python -m repro.launch.fleet --streams 256 --length 1024 \
        --chunk 128 --digitize-every 2 --devices 8 --pods 2
"""
from __future__ import annotations

if __name__ == "__main__":  # pragma: no cover -- CLI path only
    # Must precede the jax import below: jax locks the device count on
    # first init, and argparse can only run after the (jax-importing)
    # library half of this module loads.  Shared pre-scan with the
    # stream/transport/workload CLIs; the fleet dry-run defaults to 8.
    from repro.launch.cli import prescan_host_devices

    prescan_host_devices(default="8")

import argparse
import functools
import time
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.receiver import delta_frame_bytes
from repro.core.symed import (
    SymEDConfig, symed_encode, symed_receive_chunk, symed_receive_finish,
)
from repro.launch.mesh import make_pod_data_mesh
from repro.utils.jax_compat import make_mesh, shard_map

__all__ = [
    "fleet_data_mesh", "resolve_fleet_mesh", "describe_ingestion",
    "validate_cli_args", "run_fleet", "fleet_report", "main",
]

AxisSpec = Union[str, Sequence[str]]


def fleet_data_mesh(n_devices: Optional[int] = None):
    """1-D ``(data,)`` mesh over exactly the first ``n_devices`` (default:
    all).  Asking for more devices than the platform has is an error: a
    ``--devices 8`` run on a one-chip machine must not quietly shrink."""
    have = jax.device_count()
    n = n_devices or have
    if n > have:
        raise ValueError(
            f"asked for a {n}-device data mesh, but the "
            f"{jax.default_backend()} platform has {have} device(s)")
    return make_mesh((n,), ("data",), devices=jax.devices()[:n])


def resolve_fleet_mesh(n_pods: int, n_dev: int):
    """CLI helper: ``(mesh, axis, layout string)`` for a pods-aware run.

    Shared by ``repro.launch.fleet`` and ``examples/edge_fleet.py`` so the
    two CLIs cannot drift apart in how they map ``--pods`` to a mesh.
    """
    if n_dev % n_pods:
        raise ValueError(f"{n_dev} devices must divide over {n_pods} pods")
    if n_pods > 1:
        mesh = make_pod_data_mesh(n_pods, n_dev // n_pods)
        return mesh, ("pod", "data"), f"pod x data = {n_pods} x {n_dev // n_pods}"
    return fleet_data_mesh(n_dev), "data", f"data = {n_dev}"


def describe_ingestion(chunk: Optional[int], digitize_every: int) -> str:
    """Human-readable ingestion mode for the CLI reports."""
    if not chunk:
        return "whole-stream"
    cadence = (f", digitize every {digitize_every}" if digitize_every
               else ", digitize at finish")
    return f"streaming({chunk}{cadence})"


def validate_cli_args(ap: argparse.ArgumentParser, args) -> None:
    """Early validation of the streaming/fleet flags both CLIs share.

    Called before any jax work so bad invocations fail fast (exit 2 via
    ``ap.error``) instead of surfacing as tracebacks from ``run_fleet``.
    """
    from repro.launch.cli import validate_shared_args

    validate_shared_args(ap, args)
    if args.chunk is not None and args.chunk < 0:
        ap.error(f"--chunk must be >= 0 (0 = whole-stream), got {args.chunk}")
    if args.chunk and args.chunk > args.length:
        ap.error(f"--chunk {args.chunk} exceeds --length {args.length}: "
                 "the ingestion window cannot outgrow the stream")
    if args.digitize_every and not args.chunk:
        ap.error("--digitize-every requires --chunk (streaming mode)")
    if args.pods < 1:
        ap.error(f"--pods must be >= 1, got {args.pods}")


def _encode_slab(slab, keys, cfg: SymEDConfig, chunk_len, digitize_every_k,
                 reconstruct):  # symlint: hot-path
    """Per-shard body: vmapped SymED over a local (b, T) sub-slab.

    Returns ``(out, wire_out)``: ``wire_out`` (b,) is the outbound
    symbol-delta traffic each stream's receiver would put on the wire --
    one frame per digitize pass plus the closing frame at end-of-stream
    (``repro.launch.stream``'s emitter; whole-stream ingestion degenerates
    to a single closing frame carrying every symbol).
    """
    if chunk_len is None:
        out = jax.vmap(lambda t, k: symed_encode(t, cfg, k, reconstruct))(
            slab, keys)
        return out, delta_frame_bytes(out["n_pieces"])

    # streaming receiver: only the current window + the O(n_max) ReceiverState
    # are live; the loop unrolls over the static window count.  The digitize
    # cadence is resolved *here*, per window, rather than letting the traced
    # ``chunks % k`` cond do it: under vmap a cond lowers to select, which
    # would run the O(n_max) digitizer scan on every window and merely discard
    # the off-cadence results -- deciding host-side keeps the k-means cost at
    # the intended T/(C*k) per stream.  ``(i + 1) % k`` mirrors the in-state
    # ``chunks`` counter exactly, so outputs are unchanged.
    t_len = slab.shape[-1]
    dk = digitize_every_k or 0
    state = None
    wire_out = jnp.zeros((slab.shape[0],), jnp.float32)
    for i, c in enumerate(range(0, t_len, chunk_len)):
        window = slab[:, c: c + chunk_len]
        dk_i = 1 if dk and (i + 1) % dk == 0 else 0
        if state is None:
            state, info = jax.vmap(
                lambda w, k: symed_receive_chunk(w, cfg, None, k,
                                                 digitize_every_k=dk_i)
            )(window, keys)
        else:
            state, info = jax.vmap(
                lambda w, s: symed_receive_chunk(w, cfg, s,
                                                 digitize_every_k=dk_i)
            )(window, state)
        wire_out = wire_out + info["symbol_delta"]["frame_bytes"]
    n_dig_before_finish = state.dig.n
    if reconstruct:
        out = jax.vmap(
            lambda s, t: symed_receive_finish(s, cfg, t, reconstruct=True)
        )(state, slab)
    else:
        out = jax.vmap(
            lambda s: symed_receive_finish(s, cfg, None, reconstruct=False)
        )(state)
    # the closing frame: whatever the final flush digitized
    wire_out = wire_out + delta_frame_bytes(out["n_pieces"] - n_dig_before_finish)
    return out, wire_out


@functools.lru_cache(maxsize=32)
def _mapped_runner(mesh, axes: Tuple[str, ...], cfg: SymEDConfig, chunk_len,  # symlint: entry(drive=fleet, budget=0)
                   digitize_every_k, reconstruct):
    """Jitted shard_map program, cached so repeat fleet runs (benchmarks,
    chunk-by-chunk services) pay trace+compile once per configuration."""

    def hier_psum(v):
        # hierarchical telemetry tree: reduce the innermost axis first
        # (within-pod ICI), then each enclosing axis (cross-pod DCN)
        for ax in reversed(axes):
            v = jax.lax.psum(v, ax)
        return v

    def shard_fn(slab, slab_keys):
        out, wire_out = _encode_slab(slab, slab_keys, cfg, chunk_len,
                                     digitize_every_k, reconstruct)
        n_pts = jnp.float32(slab.shape[0] * slab.shape[1])
        tele = {
            "streams": hier_psum(jnp.float32(slab.shape[0])),
            "points": hier_psum(n_pts),
            "pieces": hier_psum(jnp.sum(out["n_pieces"].astype(jnp.float32))),
            "wire_bytes": hier_psum(jnp.sum(out["wire_bytes"])),
            "raw_bytes": hier_psum(n_pts * 4.0),
            "wire_out_bytes": hier_psum(jnp.sum(wire_out)),
        }
        return out, tele

    # P accepts a tuple of axis names per dim; a 1-tuple == the bare name
    return jax.jit(shard_map(
        shard_fn, mesh,
        in_specs=(P(axes, None), P(axes)),
        out_specs=(P(axes), P()),
    ))


def run_fleet(
    fleet: jax.Array,
    cfg: SymEDConfig,
    key: jax.Array,
    mesh=None,
    *,
    chunk_len: Optional[int] = None,
    digitize_every_k: Optional[int] = None,
    reconstruct: bool = False,
    axis: AxisSpec = "data",
    obs=None,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Run the SymED pipeline over ``fleet`` (n_streams, T), sharded on ``axis``.

    ``axis`` may be a single mesh axis (``"data"``) or a sequence
    (``("pod", "data")``) -- streams then shard over the flattened device grid
    of those axes and telemetry reduces hierarchically (innermost axis first).

    Each stream gets its own PRNG key (split from ``key``), so results are
    independent of the device layout: a (2, 4) pod x data mesh, an (8,) data
    mesh, and a single device produce identical outputs (tested).

    ``chunk_len=C`` switches to the streaming receiver (windows of ``C``
    points, O(n_max) carry); ``digitize_every_k=k`` additionally digitizes
    every ``k`` windows so symbols stream out online (requires ``chunk_len``).

    Returns ``(out, telemetry)``: ``out`` are the per-stream ``symed_encode``
    outputs (sharded like the input), ``telemetry`` the replicated fleet-wide
    totals reduced on-mesh: ``streams``, ``points``, ``pieces``,
    ``wire_bytes``, ``raw_bytes``, and ``wire_out_bytes`` -- the outbound
    symbol-delta traffic (one frame per digitize pass plus the closing
    frame, ``repro.launch.stream``'s wire format).

    ``obs``: optional ``repro.obs.Observability`` bundle; when given, the
    dispatch is recorded as a ``fleet.dispatch`` span + histogram sample
    (dispatch only -- the runner returns asynchronously; block on the
    telemetry before timing end-to-end).
    """
    mesh = mesh if mesh is not None else fleet_data_mesh()
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    if not axes:
        raise ValueError("axis must name at least one mesh axis")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in axes:
        if a not in sizes:
            raise ValueError(
                f"unknown mesh axis {a!r}; mesh has axes {tuple(sizes)}"
            )
    n_shards = 1
    for a in axes:
        n_shards *= sizes[a]
    fleet = jnp.asarray(fleet, jnp.float32)
    n_streams = fleet.shape[0]
    if n_streams % n_shards:
        raise ValueError(
            f"n_streams={n_streams} must divide over {n_shards} "
            f"{'x'.join(axes)} shards"
        )
    if chunk_len is not None and chunk_len < 1:
        raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
    if digitize_every_k is not None and digitize_every_k < 0:
        raise ValueError(
            f"digitize_every_k must be >= 0, got {digitize_every_k}")
    if digitize_every_k and chunk_len is None:
        raise ValueError("digitize_every_k requires chunk_len (streaming mode)")
    keys = jax.random.split(key, n_streams)

    fleet = jax.device_put(fleet, NamedSharding(mesh, P(axes, None)))
    keys = jax.device_put(keys, NamedSharding(mesh, P(axes)))

    runner = _mapped_runner(mesh, axes, cfg, chunk_len, digitize_every_k,
                            reconstruct)
    obs_on = obs is not None and obs.enabled
    t_disp = time.perf_counter_ns() if obs_on else 0
    with mesh:
        out, tele = runner(fleet, keys)
    if obs_on:
        obs.metrics.histogram(
            "fleet_dispatch_seconds", "run_fleet dispatch latency "
            "(trace/compile on first call at a shape)", unit="ns"
        ).observe(time.perf_counter_ns() - t_disp)
        obs.tracer.add("fleet.dispatch", t_disp,
                       {"streams": n_streams, "shards": n_shards})
    return out, tele


def fleet_report(tele: Dict[str, jax.Array], wall_seconds: float,
                 obs=None) -> Dict[str, object]:
    """Host-side summary: telemetry totals + wall-clock rates.

    ``obs``: optional ``repro.obs.Observability`` bundle.  When given, the
    fleet totals are published as gauges on its registry (so a scrape of a
    long-lived driver sees the wire/throughput story) and its JSON snapshot
    is merged under the report's ``"obs"`` key.

    Robust to empty fleets (zero streams / zero points): every ratio is
    clamped, so the report never divides by zero.  ``ms_per_symbol`` is the
    paper's per-symbol conversion latency metric (42ms/symbol in the paper's
    single-CPU setup; amortized here over the whole fleet run).

    Wire telemetry covers both directions, with the same keys
    ``StreamServer.report`` uses: ``wire_in_bytes``/``wire_in_ratio`` is the
    sender->receiver traffic against the raw stream (the paper's headline
    9.5% compression of network traffic; here the 4 B/piece endpoints +
    hello, i.e. ``wire_bytes``), ``wire_out_bytes``/``wire_out_ratio`` the
    receiver's outbound symbol-delta frames.  Both ratios share the
    ``raw_bytes`` denominator: outbound frames against the *compressed*
    inbound bytes read > 1.0 on short cadence windows (frame headers swamp
    the already-reduced denominator) even when the service is cutting
    traffic, so the out-ratio, like the in-ratio, answers "what fraction of
    the original signal's bytes crossed this hop".
    """
    t = {k: float(v) for k, v in tele.items()}
    dt = max(wall_seconds, 1e-9)
    rep: Dict[str, object] = {
        **t,
        "wall_seconds": wall_seconds,
        "points_per_s": t["points"] / dt,
        "pieces_per_s": t["pieces"] / dt,
        "streams_per_s": t["streams"] / dt,
        "ms_per_symbol": 1e3 * dt / max(t["pieces"], 1.0),
        "compression_rate": t["wire_bytes"] / max(t["raw_bytes"], 1.0),
        "mean_pieces_per_stream": t["pieces"] / max(t["streams"], 1.0),
        "wire_in_bytes": t["wire_bytes"],
        "wire_in_ratio": t["wire_bytes"] / max(t["raw_bytes"], 1.0),
        # wire-out telemetry is absent from pre-delta callers' dicts
        "wire_out_bytes": t.get("wire_out_bytes", 0.0),
        "wire_out_ratio": t.get("wire_out_bytes", 0.0) / max(t["raw_bytes"], 1.0),
    }
    if obs is not None and obs.enabled:
        m = obs.metrics
        for key in ("streams", "points", "pieces", "wire_bytes", "raw_bytes",
                    "wire_out_bytes"):
            if key in t:
                m.gauge(f"fleet_{key}", "fleet telemetry total").set(t[key])
        rep["obs"] = obs.snapshot()
    return rep


def main():
    from repro.launch.cli import (
        add_devices_arg, add_metrics_args, add_symed_args)

    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--streams", type=int, default=256)
    ap.add_argument("--length", type=int, default=1024)
    ap.add_argument("--chunk", type=int, default=None,
                    help="streaming-receiver ingestion window "
                         "(default / 0: whole stream)")
    ap.add_argument("--digitize-every", type=int, default=0,
                    help="digitize cadence k: run the receiver's clustering "
                         "every k windows so symbols stream out online "
                         "(0: once at end-of-stream; requires --chunk)")
    ap.add_argument("--pods", type=int, default=1,
                    help="shard over a (pod, data) mesh with this many pods "
                         "(hierarchical telemetry reduction)")
    ap.add_argument("--reconstruct", action="store_true",
                    help="also reconstruct + score DTW error (slower)")
    add_devices_arg(ap, default=8,
                    help="devices in the data mesh (on the CPU platform, "
                         "also the forced host device count)")
    add_symed_args(ap)
    add_metrics_args(ap)
    args = ap.parse_args()

    validate_cli_args(ap, args)
    if args.devices % args.pods:
        ap.error(f"--devices {args.devices} must divide over "
                 f"--pods {args.pods}")

    from repro.data.synthetic import make_fleet
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    n_dev = args.devices
    mesh, mesh_axes, layout = resolve_fleet_mesh(args.pods, n_dev)
    streams = max(args.streams - args.streams % n_dev, n_dev)
    cfg = SymEDConfig(tol=args.tol, alpha=args.alpha, n_max=256, k_max=32,
                      len_max=256)
    fleet = make_fleet(streams, args.length, seed=args.seed)

    from repro.obs import Observability

    obs = Observability()
    exporter = None
    if args.metrics_port is not None:
        from repro.obs.export import start_exporter
        exporter = start_exporter(obs, args.metrics_port)
        print(f"metrics exporter        : {exporter.url}/metrics")
    t0 = time.perf_counter()
    out, tele = run_fleet(
        fleet, cfg, jax.random.key(args.seed), mesh,
        chunk_len=args.chunk or None,
        digitize_every_k=args.digitize_every or None,
        reconstruct=args.reconstruct, axis=mesh_axes, obs=obs,
    )
    jax.block_until_ready(tele["pieces"])
    rep = fleet_report(tele, time.perf_counter() - t0, obs=obs)

    mode = describe_ingestion(args.chunk, args.digitize_every)
    print(f"devices / data shards   : {n_dev}")
    print(f"mesh layout             : {layout}")
    print(f"ingestion               : {mode}")
    print(f"streams                 : {streams} x {args.length} points")
    print(f"wall time               : {rep['wall_seconds']:.2f}s")
    print(f"throughput              : {rep['points_per_s'] / 1e6:.2f} Mpoints/s, "
          f"{rep['pieces_per_s']:.0f} pieces/s")
    print(f"symbol latency          : {rep['ms_per_symbol']:.3f} ms/symbol "
          f"(paper: 42ms single-CPU)")
    print(f"fleet pieces            : {int(rep['pieces'])} "
          f"({rep['mean_pieces_per_stream']:.1f}/stream)")
    print(f"fleet raw bytes         : {int(rep['raw_bytes']):,}")
    print(f"fleet wire-in bytes     : {int(rep['wire_in_bytes']):,} "
          f"(ratio {rep['wire_in_ratio']:.4f})")
    print(f"fleet wire-out bytes    : {int(rep['wire_out_bytes']):,} "
          f"(symbol-delta frames)")
    print(f"compression rate        : {rep['compression_rate']:.6f} "
          f"(paper avg 0.095)")
    if args.reconstruct:
        print(f"mean DTW err (pieces)   : {np.asarray(out['re_pieces']).mean():.3f}")
        print(f"mean DTW err (symbols)  : {np.asarray(out['re_symbols']).mean():.3f}")
    if args.trace_out:
        obs.tracer.write(args.trace_out)
        print(f"trace written           : {args.trace_out} "
              f"({obs.tracer.recorded} events, load at ui.perfetto.dev)")
    if exporter is not None:
        if args.metrics_linger:
            print(f"metrics exporter        : lingering "
                  f"{args.metrics_linger:.0f}s for scrapes", flush=True)
            time.sleep(args.metrics_linger)
        exporter.close()


if __name__ == "__main__":
    main()

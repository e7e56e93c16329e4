"""Resident SymED session service: the paper's deployment shape as a driver.

The paper's receiver is a *long-lived* process: compressed points arrive over
the network, symbols leave in real time (42 ms/symbol in the paper's
single-CPU setup).  ``repro.launch.fleet`` replays pre-materialized slabs;
this module keeps the state *resident* instead.  A ``StreamServer`` owns a
slot table of ``max_sessions`` batched ``ReceiverState``s (one slot per live
stream) and drives every arrival through **one donated-jit batched step**
(``jax.vmap`` of ``symed_receive_masked_chunk``): ragged arrivals are padded
to the ``window_cap`` with per-slot valid counts, fresh and resumed sessions
share the same program (seeding is a runtime branch), and idle slots ride
along as masked no-ops.  Donation means the table's device buffers are
updated in place call after call -- the service's steady-state allocates
nothing.

Wire out: every digitize pass emits a **symbol-delta frame**
``(new_labels, new_piece_endpoints, n_new)`` -- only what changed since the
previous call (ABBA-VSM-style downstream consumers ingest the symbol stream
incrementally).  The frames are self-concatenating: joining every delta of a
session plus its closing frame reproduces ``symed_finish``'s
``symbols_online`` / wire endpoints **bitwise** (property battery in
``tests/test_stream_service.py``).

An online DTW monitor (``dtw_every=m``) scores each session's
piece-reconstruction against the raw points seen so far every ``m`` windows
(``reconstruct_from_pieces`` + ``kernels.ops.dtw``), so a drifting sender is
visible while the stream is still live.

Slot lifecycle: ``open`` allocates a free slot (or, with ``evict_idle``,
closes the least-recently-active session to make room -- its final output is
parked in ``server.evicted``); ``close`` flushes the tail, emits the closing
delta frame, and frees the slot for reuse.

CLI (trace-driven; arrivals come from a ``repro.workload`` trace --
``--workload`` names a scenario or a recorded ``workload_trace/v1`` jsonl,
and the legacy ``--arrival-pattern`` values are deprecated shims that
synthesize the equivalent trace.  ``--devices N`` forces N host CPU devices
and shards the slot table over a ``data`` mesh axis):

    PYTHONPATH=src python -m repro.launch.stream --sessions 6 --max-slots 4 \
        --length 384 --window 48 --workload bursty --evict --verify
"""
from __future__ import annotations

if __name__ == "__main__":  # pragma: no cover -- CLI path only
    # Must precede the jax import below (jax locks the device count on
    # first init); shared pre-scan with the fleet/transport/workload CLIs.
    from repro.launch.cli import prescan_host_devices

    prescan_host_devices()

import argparse
import contextlib
import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.receiver import (
    DELTA_FRAME_HEADER_BYTES, DELTA_SYMBOL_BYTES, PIECE_TUPLE_BYTES,
    pieces_from_wire,
)
from repro.core.reconstruct import reconstruct_from_pieces
from repro.core.symed import (
    SymEDConfig, receiver_init, symbols_to_string, symed_receive_finish,
    symed_receive_masked_chunk_table, symed_receive_masked_pieces_table,
)
from repro.kernels import ops
from repro.obs import Observability, as_obs
from repro.utils.jax_compat import shard_map, trace_annotation

__all__ = ["StreamServer", "main"]

# Shared inert context for the non-annotated dispatch path: nullcontext is
# stateless and reentrant, so one instance serves every round.
_NULL_ANN_CTX = contextlib.nullcontext()


def _null_annotation(name: str):
    return _NULL_ANN_CTX


def _per_shard(fn, mesh):
    """Run a slot-table function on each ``data`` shard's own slots.

    Slots are independent, so a sharded table steps shard-locally with no
    communication.  This is also what lets the Pallas kernel run on a mesh:
    XLA cannot partition a Mosaic kernel call on its own.
    """
    if mesh is None:
        return fn
    return shard_map(fn, mesh, in_specs=P("data"), out_specs=P("data"))


@functools.partial(
    jax.jit, static_argnames=("cfg", "digitize_every_k", "use_kernel", "mesh"),
    donate_argnums=(0,),
)
def _table_step(table, windows, n_valid, *, cfg, digitize_every_k,  # symlint: entry(drive=stream, budget=0, shapes=table-step)
                use_kernel=False, mesh=None):
    """One batched service step: every slot ingests its padded window.

    The table-level receive fuses the digitize pass across slots (one
    cursor loop sized by the widest span of new pieces, Pallas Lloyd
    half-steps when ``use_kernel``); the sender half vmaps per slot.  All
    loop-varying quantities (windows, valid counts, the in-state cadence
    clock) are runtime operands -- only capacity changes retrace.  With a
    ``mesh``, every shard steps its own slots (``_per_shard``).
    """
    def step(table, windows, n_valid):
        return symed_receive_masked_chunk_table(
            windows, n_valid, cfg, table,
            digitize_every_k=digitize_every_k, use_kernel=use_kernel,
        )

    return _per_shard(step, mesh)(table, windows, n_valid)


@functools.partial(
    jax.jit, static_argnames=("cfg", "digitize_every_k", "use_kernel", "mesh"),
    donate_argnums=(0,),
)
def _table_step_pieces(table, endpoints, steps, n_valid, hello, t_seen, *,  # symlint: entry(drive=stream, budget=0, shapes=table-step-pieces)
                       cfg, digitize_every_k, use_kernel=False, mesh=None):
    """Compressed-in service step: every slot scatters its padded pieces."""
    def step(table, endpoints, steps, n_valid, hello, t_seen):
        return symed_receive_masked_pieces_table(
            endpoints, steps, n_valid, hello, t_seen, cfg, table,
            digitize_every_k=digitize_every_k, use_kernel=use_kernel,
        )

    return _per_shard(step, mesh)(table, endpoints, steps, n_valid, hello,
                                  t_seen)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slot(table, slot, blank):
    """Reset one slot of the table to a blank state (open / reopen)."""
    return jax.tree.map(lambda l, b: l.at[slot].set(b), table, blank)


@jax.jit
def _read_slot(table, slot):
    """Extract one slot's ReceiverState (for finish / monitoring)."""
    return jax.tree.map(lambda l: l[slot], table)


@jax.jit
def _gather_slots(table, perm):
    """Reorder/resize the table by gathering ``perm`` (autoscale shrink).

    A pure gather: slot states move bitwise-unchanged, so the delta-
    concatenation contract is untouched by any resize point.  Not donated --
    the output shape differs from the input's.
    """
    return jax.tree.map(lambda l: l[perm], table)


@jax.jit
def _concat_slots(table, blanks):
    """Append blank slots to the table (autoscale grow)."""
    return jax.tree.map(
        lambda l, b: jnp.concatenate([l, b], axis=0), table, blanks)


def _new_delta() -> dict:
    """Empty merged symbol-delta accumulator (one per sid per ingest call)."""
    return {"labels": [], "endpoints": [], "n_new": 0, "frames": 0,
            "bytes": 0.0}


def _finalize_deltas(deltas: Dict[str, dict]) -> Dict[str, dict]:
    """Concatenate each accumulator's per-round slices into flat arrays."""
    for out in deltas.values():
        out["labels"] = (np.concatenate(out["labels"])
                         if out["labels"] else np.zeros((0,), np.int32))
        out["endpoints"] = (np.concatenate(out["endpoints"])
                            if out["endpoints"] else np.zeros((0,), np.float32))
    return deltas


def symbol_latency_histogram(metrics):
    """The paper's per-symbol latency series (get-or-create, so a transport
    in front of the server records into the same instrument)."""
    return metrics.histogram(
        "symed_symbol_latency_seconds",
        "per-symbol latency: window arrival to delta-frame emit "
        "(the paper's 42 ms metric)", unit="ns")


@dataclasses.dataclass
class _Session:
    """Host-side bookkeeping for one live slot (device state is the table)."""

    stream_id: str
    slot: int
    chunks: int = 0           # non-empty windows ingested
    t_seen: int = 0           # stream points ingested
    symbols_out: int = 0      # symbols emitted across delta frames
    frames_out: int = 0       # delta frames emitted
    bytes_out: float = 0.0    # outbound delta-frame bytes
    last_active: int = 0      # server clock at last arrival (LRU eviction)
    raw: Optional[List[np.ndarray]] = None  # raw points (DTW monitor only)
    dtw: Optional[float] = None             # latest monitor reading


class StreamServer:
    """Session-table SymED service: resident ``ReceiverState`` per stream.

    ``open(stream_id)`` allocates a slot, ``ingest(stream_id, window)``
    feeds a ragged arrival through the donated batched step and returns the
    symbol-delta frame it produced, ``close(stream_id)`` flushes the stream
    and frees the slot.  All sessions advance together: ``ingest_many``
    batches concurrent arrivals into a single device program.

    Args:
      cfg: SymED hyperparameters (shared by every session).
      max_sessions: slot-table capacity (static; the batched step's shape).
      window_cap: padded arrival width.  Longer arrivals are split into
        ``window_cap``-sized rounds host-side; shorter ones are padded and
        masked, so any arrival size works without retracing.
      digitize_every_k: digitize cadence in non-empty windows per session
        (``symed_receive_chunk`` semantics; 0 defers symbols to ``close``).
      dtw_every: every this-many windows per session, reconstruct from the
        accumulated pieces and score DTW against the raw points seen so far
        (0 disables; enabling keeps each session's raw history on the host).
      dtw_band: Sakoe-Chiba radius for the monitor (None = full DTW).
      evict_idle: when the table is full *and cannot grow further*, ``open``
        evicts the least-recently active session (final output parked in
        ``server.evicted``) instead of raising.
      autoscale: grow/shrink the donated slot table between steps.  The
        capacity walks a power-of-two ladder from ``min_slots`` up to
        ``max_sessions``: ``open`` on a full table doubles it (carrying every
        live state), ``close``/eviction shrinks it once occupancy falls to a
        quarter of the current size (live slots are compacted with a pure
        gather, so states move bitwise-unchanged and the delta-concatenation
        contract holds across every resize point).  Eviction only fires at
        ``max_sessions``.  Each distinct capacity traces the batched step
        once (between-steps cost, amortized at steady state).
      min_slots: autoscale floor (default: the mesh device count, else 1).
      shrink_patience: autoscale hysteresis -- shrink only after this many
        *consecutive* low-occupancy observations (closes / ingest rounds).
        A session count oscillating across the quarter-occupancy boundary
        would otherwise alternate grow/shrink every tick, re-gathering the
        slot table each time.  ``1`` restores the immediate-shrink behavior.
        Resizes never touch slot contents (pure gather/concat), so delta
        streams are bitwise-unaffected by the setting (property tested).
      use_kernel: route the digitize pass's Lloyd half-steps through the
        fused Pallas k-means kernel, one ``pallas_call`` per iteration for
        the whole slot table (default: on for TPU backends, off on CPU
        where the bitwise vmapped reference is also the fastest lowering).
      pretrace: trace + compile the batched step for *every* capacity on
        the autoscale ladder at construction time (one donated call per
        rung on blank tables), so no ingest round ever pays a trace: grows
        and shrinks hit the jit cache.  Off by default -- tests and
        short-lived drivers would pay ladder-warmup for rungs they never
        visit; the CLI and benchmarks turn it on.
      seed: base PRNG seed for per-session digitizer keys.
      mesh: optional 1-D ``(data,)`` mesh; the slot table shards over it
        (``max_sessions``, ``min_slots`` and every ladder capacity must
        divide over the mesh devices).
      obs: the flight recorder (``repro.obs``).  ``None`` (default) makes a
        fresh enabled ``Observability`` bundle; ``False`` disables recording
        entirely (shared null instruments, zero per-round cost); passing a
        bundle lets layered components (e.g. the transport front end) share
        one registry -- but each registry admits only *one* ``StreamServer``
        (the totals-backed callback series are per-server).  Recording is
        host-side integer arithmetic only, so the ingest hot path stays
        sync-free; the instrumented-vs-disabled tick overhead is gated at
        <= 5% by ``benchmarks/check_bench.py``.
    """

    def __init__(
        self,
        cfg: SymEDConfig,
        *,
        max_sessions: int = 8,
        window_cap: int = 64,
        digitize_every_k: int = 1,
        dtw_every: int = 0,
        dtw_band: Optional[int] = None,
        evict_idle: bool = False,
        autoscale: bool = False,
        min_slots: Optional[int] = None,
        shrink_patience: int = 3,
        use_kernel: Optional[bool] = None,
        pretrace: bool = False,
        seed: int = 0,
        mesh=None,
        obs=None,
    ):
        if max_sessions < 1:
            raise ValueError(f"max_sessions must be >= 1, got {max_sessions}")
        if window_cap < 1:
            raise ValueError(f"window_cap must be >= 1, got {window_cap}")
        if digitize_every_k < 0:
            raise ValueError(
                f"digitize_every_k must be >= 0, got {digitize_every_k}")
        if dtw_every < 0:
            raise ValueError(f"dtw_every must be >= 0, got {dtw_every}")
        if mesh is not None and max_sessions % mesh.devices.size:
            raise ValueError(
                f"max_sessions={max_sessions} must divide over the "
                f"{mesh.devices.size}-device mesh")
        if min_slots is None:
            min_slots = mesh.devices.size if mesh is not None else 1
        if not 1 <= min_slots <= max_sessions:
            raise ValueError(
                f"min_slots={min_slots} must be in [1, {max_sessions}]")
        if mesh is not None and min_slots % mesh.devices.size:
            raise ValueError(
                f"min_slots={min_slots} must divide over the "
                f"{mesh.devices.size}-device mesh")
        if shrink_patience < 1:
            raise ValueError(
                f"shrink_patience must be >= 1, got {shrink_patience}")
        self.cfg = cfg
        self.max_sessions = int(max_sessions)
        self.window_cap = int(window_cap)
        self.digitize_every_k = int(digitize_every_k)
        self.dtw_every = int(dtw_every)
        self.dtw_band = dtw_band
        self.evict_idle = bool(evict_idle)
        self.autoscale = bool(autoscale)
        self.min_slots = int(min_slots)
        self.shrink_patience = int(shrink_patience)
        self._low_ticks = 0         # consecutive low-occupancy observations
        self.use_kernel = (bool(use_kernel) if use_kernel is not None
                           else not ops.on_cpu())
        # capacity ladder: min_slots * 2^i, clipped at max_sessions
        self._ladder = [self.min_slots]
        while self._ladder[-1] < self.max_sessions:
            self._ladder.append(min(self._ladder[-1] * 2, self.max_sessions))
        self.capacity = self.min_slots if autoscale else self.max_sessions
        self._mesh = mesh
        self._base_key = jax.random.key(seed)
        self._serial = 0            # sessions ever opened (key derivation)
        self._clock = 0             # ingest rounds (LRU ordering)
        self._sessions: Dict[str, _Session] = {}
        self._dtw_due: set = set()  # sessions whose DTW cadence fired
        self._free = list(range(self.capacity))
        self.evicted: Dict[str, dict] = {}
        # fleet-wide wire accounting (the service's fleet_report counterpart)
        self.totals = {
            "points_in": 0, "bytes_in": 0.0, "symbols_out": 0,
            "frames_out": 0, "bytes_out": 0.0, "steps": 0,
            "opened": 0, "closed": 0, "evicted": 0,
            "grows": 0, "shrinks": 0,
        }
        self._table = self._shard(self._blanks(self.capacity))
        if pretrace:
            self._pretrace_ladder()
        self.obs = as_obs(obs)
        self._obs_on = self.obs.enabled
        self._ann = (trace_annotation if self.obs.jax_annotate
                     else _null_annotation)
        # retrace accounting baseline: jit cache entries at construction
        # (module-level cache, so the counter reports compiles observed by
        # *this* server since its init -- incl. first-touch rungs when
        # pretrace is off)
        self._compiled_base = self._cache_entries()
        self._compiled_seen = self._compiled_base
        self._register_metrics()

    @staticmethod
    def _cache_entries() -> int:
        return int(_table_step._cache_size() + _table_step_pieces._cache_size())

    def _note_compiles(self) -> None:
        """Drop an instant trace event when the jit cache grew this round.

        A growing cache during serving means a retrace the pretrace ladder
        did not cover -- exactly the event worth seeing on the timeline.
        Cost when nothing changed: two cache-size reads (dict lens).
        """
        cs = self._cache_entries()
        if cs > self._compiled_seen:
            self.obs.tracer.instant("stream.retrace", {"compiled": cs})
            self._compiled_seen = cs

    def _register_metrics(self) -> None:
        """Wire the flight recorder to this server.

        Histograms are recorded in the serving loop (integer bucket adds);
        everything already counted in ``self.totals`` is exposed as
        scrape-time callback series instead -- zero added hot-path work.
        """
        m = self.obs.metrics
        self._h_symbol_lat = symbol_latency_histogram(m)
        self._h_tick = m.histogram(
            "symed_ingest_tick_seconds",
            "per-round ingest latency: pack + dispatch + harvest", unit="ns")
        # the digitize loop's work, read from each step's outputs at harvest
        self._m_trips = m.counter(
            "symed_digitize_trips_total",
            "table-step digitize trips (each step: its widest span)")
        self._m_rounds = m.counter(
            "symed_kgrowth_rounds_total",
            "table-step k-growth rounds run for kept lanes, summed over "
            "each step's digitize trips")
        runs_help = ("table-step Lloyd k-means runs times lanes: executed "
                     "(every lane), useful (lanes digitizing or growing k)")
        self._m_lane_runs = m.counter("symed_lloyd_lane_runs_total",
                                      runs_help, labels={"kind": "executed"})
        self._m_useful_runs = m.counter("symed_lloyd_lane_runs_total",
                                        runs_help, labels={"kind": "useful"})
        if not self._obs_on:
            return
        t = self.totals
        for key, name, help_text in (
            ("points_in", "symed_points_in_total", "raw points ingested"),
            ("bytes_in", "symed_wire_in_bytes_total", "inbound wire bytes"),
            ("symbols_out", "symed_symbols_out_total", "symbols emitted"),
            ("frames_out", "symed_frames_out_total", "delta frames emitted"),
            ("bytes_out", "symed_wire_out_bytes_total", "outbound wire bytes"),
            ("steps", "symed_batched_steps_total", "donated table steps run"),
            ("opened", "symed_sessions_opened_total", "sessions opened"),
            ("closed", "symed_sessions_closed_total", "sessions closed"),
            ("evicted", "symed_sessions_evicted_total", "sessions LRU-evicted"),
            ("grows", "symed_table_grows_total", "autoscale ladder grows"),
            ("shrinks", "symed_table_shrinks_total", "autoscale ladder shrinks"),
        ):
            m.counter_fn(name, help_text,
                         (lambda k=key: float(t[k])))
        m.gauge_fn("symed_active_sessions", "open sessions",
                   lambda: float(len(self._sessions)))
        m.gauge_fn("symed_table_capacity", "slot-table capacity",
                   lambda: float(self.capacity))
        m.counter_fn("symed_table_retraces_total",
                     "batched-step compiles observed since server init",
                     lambda: float(max(self._cache_entries()
                                       - self._compiled_base, 0)))

    def _pretrace_ladder(self) -> None:
        """Warm the jit cache for every capacity on the autoscale ladder.

        AOT ``lower().compile()`` would not populate the call cache jit
        actually consults, so each rung makes one real (donated) call on a
        blank table with zero-valid windows -- a masked no-op that leaves no
        state behind.  After this, grow/shrink during serving never traces
        (asserted flat by ``tests/test_stream_service.py`` via
        ``_table_step._cache_size()``).
        """
        ladder = self._ladder if self.autoscale else [self.capacity]
        for cap in ladder:
            blanks = self._shard(self._blanks(cap))
            win_f = self._put(jnp.zeros((cap, self.window_cap), jnp.float32))
            win_i = self._put(jnp.zeros((cap, self.window_cap), jnp.int32))
            cnt = self._put(jnp.zeros((cap,), jnp.int32))
            scal_f = self._put(jnp.zeros((cap,), jnp.float32))
            scal_i = self._put(jnp.zeros((cap,), jnp.int32))
            blanks, _ = _table_step(
                blanks, win_f, cnt,
                cfg=self.cfg, digitize_every_k=self.digitize_every_k,
                use_kernel=self.use_kernel, mesh=self._mesh)
            _table_step_pieces(
                blanks, win_f, win_i, cnt, scal_f, scal_i,
                cfg=self.cfg, digitize_every_k=self.digitize_every_k,
                use_kernel=self.use_kernel, mesh=self._mesh)

    def _blanks(self, n: int):
        """``n`` fresh blank slots (keys are placeholders; ``open`` reseeds)."""
        return jax.vmap(lambda k: receiver_init(self.cfg, k))(
            jax.random.split(self._base_key, n))

    def _shard(self, table):
        if self._mesh is not None:
            table = jax.device_put(
                table, NamedSharding(self._mesh, P("data")))
        return table

    def _put(self, arr):
        """Stage one slot-axis operand (sharded over the mesh if present)."""
        if self._mesh is not None:
            arr = jax.device_put(arr, NamedSharding(self._mesh, P("data")))
        return arr

    # ------------------------------------------------------------------ API

    @property
    def active_sessions(self) -> int:
        return len(self._sessions)

    def __contains__(self, stream_id: str) -> bool:
        return stream_id in self._sessions

    def session_ids(self) -> List[str]:
        """Open session ids, in open order (monitoring surface)."""
        return list(self._sessions)

    def session_stats(self, stream_id: str) -> dict:
        """Live bookkeeping for one open session (monitoring surface)."""
        sess = self._sessions[stream_id]
        return {
            "slot": sess.slot, "chunks": sess.chunks, "t_seen": sess.t_seen,
            "symbols_out": sess.symbols_out, "frames_out": sess.frames_out,
            "bytes_out": sess.bytes_out, "dtw": sess.dtw,
        }

    def open(self, stream_id: str, key: Optional[jax.Array] = None) -> int:
        """Allocate a slot for ``stream_id``; returns the slot index.

        ``key`` seeds the session's digitizer (default: derived from the
        server seed and the session serial, so every session is independent
        and reproducible).
        """
        if stream_id in self._sessions:
            raise ValueError(f"session {stream_id!r} is already open")
        t_open = time.perf_counter_ns() if self._obs_on else 0
        if not self._free and self.capacity < self.max_sessions:
            self._grow()
        if not self._free:
            if not self.evict_idle:
                raise RuntimeError(
                    f"session table full ({self.max_sessions} slots); "
                    "close a session or construct with evict_idle=True")
            lru = min(self._sessions.values(), key=lambda s: s.last_active)
            self.obs.tracer.instant("stream.evict", {"session": lru.stream_id})
            self.evicted[lru.stream_id] = self.close(lru.stream_id)
            self.totals["evicted"] += 1
            self.totals["closed"] -= 1  # eviction is not a clean close
        slot = self._free.pop()
        self._serial += 1
        if key is None:
            key = jax.random.fold_in(self._base_key, self._serial)
        self._table = _write_slot(
            self._table, jnp.asarray(slot, jnp.int32),
            receiver_init(self.cfg, key))
        self._sessions[stream_id] = _Session(
            stream_id=stream_id, slot=slot, last_active=self._clock,
            raw=[] if self.dtw_every else None,
        )
        self.totals["opened"] += 1
        self.totals["bytes_in"] += 4.0  # the t0 "hello" payload
        if self._obs_on:
            self.obs.tracer.add("stream.open", t_open)
        return slot

    def ingest(self, stream_id: str, window) -> dict:
        """Feed one ragged arrival; returns its symbol-delta frame."""
        return self.ingest_many({stream_id: window})[stream_id]

    def ingest_many(self, arrivals: Dict[str, object], *,  # symlint: hot-path
                    record_latency: bool = True) -> Dict[str, dict]:
        """Feed concurrent arrivals through one batched step per round.

        ``arrivals`` maps open stream ids to 1-D float windows of any
        length; windows longer than ``window_cap`` are split into
        consecutive rounds so every session advances in lockstep.  Returns
        the merged symbol-delta frame per stream:
        ``{"labels", "endpoints", "n_new", "frames", "bytes"}``.

        Rounds are double-buffered against the device: round ``r`` is
        dispatched (async), round ``r+1`` is packed host-side, and only
        then is round ``r``'s output transferred back -- host staging and
        accounting overlap device work instead of serializing with it.

        Every symbol returned counts in ``symed_symbol_latency_seconds``
        from this call to its return; ``record_latency=False`` leaves that
        to a caller that stamps the window's arrival and the frame's emit
        itself (the transport: frame read to DELTA write).
        """
        t_call = time.perf_counter_ns() if self._obs_on else 0
        wins = {}
        for sid, w in arrivals.items():
            if sid not in self._sessions:
                raise KeyError(f"unknown session {sid!r} (open it first)")
            w = np.asarray(w, np.float32).reshape(-1)
            wins[sid] = w
        deltas = {sid: _new_delta() for sid in wins}
        rounds = max(
            (len(w) + self.window_cap - 1) // self.window_cap
            for w in wins.values()
        ) if wins else 0
        obs_on = self._obs_on
        tracer = self.obs.tracer
        pend_active, pend_info, pend_clock = [], None, 0  # round in flight
        pend_t0 = 0  # arrival stamp of the round in flight (obs)
        for r in range(rounds):
            t_arrive = time.perf_counter_ns() if obs_on else 0
            padded = np.zeros((self.capacity, self.window_cap), np.float32)
            n_valid = np.zeros((self.capacity,), np.int32)
            active = []
            for sid, w in wins.items():
                part = w[r * self.window_cap: (r + 1) * self.window_cap]
                if not len(part):
                    continue
                sess = self._sessions[sid]
                padded[sess.slot, : len(part)] = part
                n_valid[sess.slot] = len(part)
                active.append((sid, part))
            if active:
                windows = self._put(jnp.asarray(padded))
                counts = self._put(jnp.asarray(n_valid))
                if obs_on:
                    tracer.add("stream.pack", t_arrive,
                               {"round": r, "sessions": len(active)})
                t_disp = time.perf_counter_ns() if obs_on else 0
                with self._ann("symed.table_step"):
                    self._table, info = _table_step(
                        self._table, windows, counts,
                        cfg=self.cfg, digitize_every_k=self.digitize_every_k,
                        use_kernel=self.use_kernel, mesh=self._mesh)
                if obs_on:
                    tracer.add("stream.dispatch", t_disp)
                    self._note_compiles()
                self.totals["steps"] += 1
                self._clock += 1
            # harvest the *previous* round only after this one is in flight
            if pend_active:
                self._harvest_round(pend_active, pend_info, pend_clock,
                                    deltas, pend_t0)
            pend_active = active
            if active:
                pend_info, pend_clock, pend_t0 = info, self._clock, t_arrive
        if pend_active:
            self._harvest_round(pend_active, pend_info, pend_clock, deltas,
                                pend_t0)
        self._run_dtw_monitor()
        if obs_on and record_latency:
            self._observe_latency(t_call, deltas)
        return _finalize_deltas(deltas)

    def _observe_latency(self, t0_ns: int, deltas: Dict[str, dict]) -> None:
        n = sum(d["n_new"] for d in deltas.values())
        if n:
            self._h_symbol_lat.observe_n(time.perf_counter_ns() - t0_ns, n)

    def _harvest_round(self, active, info, clock, deltas, t0_ns=0) -> None:
        """Transfer one round's outputs and fold them into the books.

        ``t0_ns`` is the round's pack start, so the tick histogram measures
        pack -> dispatch -> harvest across the double buffer.
        """
        obs_on = self._obs_on
        t_h = time.perf_counter_ns() if obs_on else 0
        d = info["symbol_delta"]
        # one blocking transfer per round, not one per output leaf
        labels, endpoints, n_new, emitted, t_seen, work = jax.device_get(  # sync: ok
            (d["labels"], d["endpoints"], d["n_new"], d["emitted"],
             info["t_seen"], info["work"] if obs_on else None))
        lat = (time.perf_counter_ns() - t0_ns) if obs_on else 0
        for sid, part in active:
            sess = self._sessions[sid]
            n = int(n_new[sess.slot])
            self._account_delta(
                sess, deltas[sid], labels[sess.slot],
                endpoints[sess.slot], n,
                bool(emitted[sess.slot]))
            sess.chunks += 1
            sess.t_seen = int(t_seen[sess.slot])
            sess.last_active = clock
            self.totals["points_in"] += len(part)
            self.totals["bytes_in"] += 4.0 * len(part)
            if sess.raw is not None:
                sess.raw.append(part)
            if (self.dtw_every and sess.raw is not None
                    and sess.chunks % self.dtw_every == 0):
                self._dtw_due.add(sid)
        if obs_on:
            self._h_tick.observe(lat)
            self.obs.tracer.add("stream.harvest", t_h,
                                {"sessions": len(active),
                                 **self._record_work(active, work)})

    def ingest_pieces_many(self, arrivals: Dict[str, dict], *,  # symlint: hot-path
                           record_latency: bool = True) -> Dict[str, dict]:
        """Compressed-in counterpart of ``ingest_many``.

        Each arrival carries pieces the *sender's* compressor finished
        (``repro.launch.transport`` pieces mode) instead of raw points:
        ``{"endpoints": (n,) f32, "steps": (n,) i32 arrival steps,
        "t_seen": int cumulative sender point clock, "t0": float hello,
        "wire_bytes": float actual inbound payload bytes (optional;
        defaults to ``PIECE_TUPLE_BYTES`` per piece)}``.  Arrivals longer
        than ``window_cap`` pieces split into consecutive rounds.  Returns
        the same merged symbol-delta dicts as ``ingest_many``.  Raw-mode and
        pieces-mode sessions may share one table (idle slots mask out of
        either batched step), but a single session must stay in one mode.
        """
        t_call = time.perf_counter_ns() if self._obs_on else 0
        pends = {}
        for sid, a in arrivals.items():
            if sid not in self._sessions:
                raise KeyError(f"unknown session {sid!r} (open it first)")
            pends[sid] = {
                "endpoints": np.asarray(a["endpoints"], np.float32).reshape(-1),
                "steps": np.asarray(a["steps"], np.int32).reshape(-1),
                "t_seen": int(a["t_seen"]),
                "t0": float(a["t0"]),
                "wire_bytes": float(a.get("wire_bytes", 0.0)),
            }
        deltas = {sid: _new_delta() for sid in pends}
        cap = self.window_cap
        rounds = max(
            ((len(p["endpoints"]) + cap - 1) // cap or 1)
            for p in pends.values()
        ) if pends else 0
        obs_on = self._obs_on
        tracer = self.obs.tracer
        pend_active, pend_info, pend_clock = [], None, 0  # round in flight
        pend_t0 = 0  # arrival stamp of the round in flight (obs)
        for r in range(rounds):
            t_arrive = time.perf_counter_ns() if obs_on else 0
            pad_e = np.zeros((self.capacity, cap), np.float32)
            pad_s = np.zeros((self.capacity, cap), np.int32)
            n_valid = np.zeros((self.capacity,), np.int32)
            hello = np.zeros((self.capacity,), np.float32)
            t_seen_in = np.zeros((self.capacity,), np.int32)
            active = []
            for sid, p in pends.items():
                part_e = p["endpoints"][r * cap: (r + 1) * cap]
                part_s = p["steps"][r * cap: (r + 1) * cap]
                if r > 0 and not len(part_e):
                    continue
                sess = self._sessions[sid]
                pad_e[sess.slot, : len(part_e)] = part_e
                pad_s[sess.slot, : len(part_s)] = part_s
                n_valid[sess.slot] = len(part_e)
                hello[sess.slot] = p["t0"]
                t_seen_in[sess.slot] = p["t_seen"]
                active.append((sid, len(part_e)))
                if r == 0:
                    wire = (p["wire_bytes"]
                            or PIECE_TUPLE_BYTES * len(p["endpoints"]))
                    self.totals["bytes_in"] += wire
            if active:
                args = [self._put(jnp.asarray(x))
                        for x in (pad_e, pad_s, n_valid, hello, t_seen_in)]
                if obs_on:
                    tracer.add("stream.pack_pieces", t_arrive,
                               {"round": r, "sessions": len(active)})
                t_disp = time.perf_counter_ns() if obs_on else 0
                with self._ann("symed.table_step_pieces"):
                    self._table, info = _table_step_pieces(
                        self._table, *args,
                        cfg=self.cfg, digitize_every_k=self.digitize_every_k,
                        use_kernel=self.use_kernel, mesh=self._mesh)
                if obs_on:
                    tracer.add("stream.dispatch_pieces", t_disp)
                    self._note_compiles()
                self.totals["steps"] += 1
                self._clock += 1
            # harvest the *previous* round only after this one is in flight
            if pend_active:
                self._harvest_pieces_round(pend_active, pend_info,
                                           pend_clock, deltas, pend_t0)
            pend_active = active
            if active:
                pend_info, pend_clock, pend_t0 = info, self._clock, t_arrive
        if pend_active:
            self._harvest_pieces_round(pend_active, pend_info, pend_clock,
                                       deltas, pend_t0)
        if obs_on and record_latency:
            self._observe_latency(t_call, deltas)
        return _finalize_deltas(deltas)

    def _harvest_pieces_round(self, active, info, clock, deltas,
                              t0_ns=0) -> None:
        """Pieces-mode counterpart of ``_harvest_round``."""
        obs_on = self._obs_on
        t_h = time.perf_counter_ns() if obs_on else 0
        d = info["symbol_delta"]
        # one blocking transfer per round, not one per output leaf
        labels, endpoints, n_new, emitted, t_seen, work = jax.device_get(  # sync: ok
            (d["labels"], d["endpoints"], d["n_new"], d["emitted"],
             info["t_seen"], info["work"] if obs_on else None))
        lat = (time.perf_counter_ns() - t0_ns) if obs_on else 0
        for sid, n_in in active:
            sess = self._sessions[sid]
            n = int(n_new[sess.slot])
            self._account_delta(
                sess, deltas[sid], labels[sess.slot],
                endpoints[sess.slot], n,
                bool(emitted[sess.slot]))
            if n_in:
                sess.chunks += 1
            now_seen = int(t_seen[sess.slot])
            self.totals["points_in"] += max(now_seen - sess.t_seen, 0)
            sess.t_seen = now_seen
            sess.last_active = clock
        if obs_on:
            self._h_tick.observe(lat)
            self.obs.tracer.add("stream.harvest_pieces", t_h,
                                {"sessions": len(active),
                                 **self._record_work(active, work)})

    def _record_work(self, active, work) -> dict:
        """Count one step's digitize work and return it as span args.

        ``work`` is the step's ``DigitizeWork`` on the host.  Each shard
        runs its own loop, ``lloyd_iters`` Lloyd calls over its lanes per
        trip and per growth round; ``trips``/``rounds`` are the step's
        largest (on one chip, its Lloyd calls are exactly ``lloyd_iters *
        (trips + rounds)``), ``lane_runs`` every lane of those calls and
        ``useful_runs`` the lanes digitizing a piece or growing k.
        ``slowest`` is the session that grew k in the most rounds.
        """
        trips, growing, rounds_run = work
        shards = self._mesh.devices.size if self._mesh is not None else 1
        per_shard = trips.shape[0] // shards
        widest = trips.reshape(shards, per_shard).max(axis=1)
        rounds = rounds_run.reshape(shards, per_shard)[:, 0]
        args = {
            "trips": int(widest.max()), "rounds": int(rounds.max()),
            "lane_runs": int(per_shard * (widest + rounds).sum()),
            "useful_runs": int(trips.sum() + growing.sum()),
            "slowest": "", "slowest_rounds": 0,
        }
        for sid, _ in active:
            g = int(growing[self._sessions[sid].slot])
            if g > args["slowest_rounds"]:
                args["slowest"], args["slowest_rounds"] = sid, g
        self._m_trips.inc(args["trips"])
        self._m_rounds.inc(args["rounds"])
        self._m_lane_runs.inc(args["lane_runs"])
        self._m_useful_runs.inc(args["useful_runs"])
        return args

    def close(self, stream_id: str) -> dict:
        """Flush the tail, emit the closing delta frame, free the slot.

        Returns ``{"out", "delta", "symbols", "n_pieces", "t_seen", "dtw"}``
        where ``out`` is the full ``symed_receive_finish`` dict (bitwise
        equal to ``symed_encode`` on the points this session ingested).
        """
        sess = self._sessions.pop(stream_id, None)
        if sess is None:
            raise KeyError(f"unknown session {stream_id!r}")
        t_close = time.perf_counter_ns() if self._obs_on else 0
        delta = {"labels": np.zeros((0,), np.int32),
                 "endpoints": np.zeros((0,), np.float32),
                 "n_new": 0, "frames": 0, "bytes": 0.0}
        out = None
        n_pieces = 0
        if sess.t_seen:  # a never-fed session has nothing to flush
            sub = _read_slot(self._table, jnp.asarray(sess.slot, jnp.int32))
            out = symed_receive_finish(sub, self.cfg, with_delta=True)
            d = out["symbol_delta"]
            n = int(d["n_new"])
            frame = DELTA_FRAME_HEADER_BYTES + DELTA_SYMBOL_BYTES * n
            delta = {"labels": np.asarray(d["labels"])[:n],
                     "endpoints": np.asarray(d["endpoints"])[:n],
                     "n_new": n, "frames": 1, "bytes": frame}
            n_pieces = int(out["n_pieces"])
            sess.symbols_out += n
            sess.frames_out += 1
            sess.bytes_out += frame
            self.totals["symbols_out"] += n
            self.totals["frames_out"] += 1
            self.totals["bytes_out"] += frame
        self._free.append(sess.slot)
        self.totals["closed"] += 1
        self._maybe_shrink()
        if self._obs_on:
            self.obs.tracer.add("stream.close", t_close,
                                {"symbols": delta["n_new"]})
        return {
            "stream_id": stream_id,
            "out": out,
            "delta": delta,
            "symbols": (symbols_to_string(out["symbols_online"], n_pieces)
                        if out is not None else ""),
            "n_pieces": n_pieces,
            "t_seen": sess.t_seen,
            "symbols_out": sess.symbols_out,
            "bytes_out": sess.bytes_out,
            "dtw": sess.dtw,
        }

    def report(self, wall_seconds: float) -> Dict[str, object]:
        """Host-side service summary (the fleet_report counterpart).

        All top-level values are floats; when the flight recorder is
        enabled, an ``"obs"`` key holds its nested JSON snapshot
        (counters / gauges / histogram digests with p50/p99/p999).

        ``wire_in_bytes``/``wire_in_ratio`` measure inbound traffic against
        the raw-points equivalent (4 B/point): ~1 for raw-in transport,
        ~``PIECE_TUPLE_BYTES / (4 * points-per-piece)`` when senders
        compress locally (the paper's 9.5%-of-raw headline is this ratio's
        sender-side half).  ``wire_out_ratio`` measures outbound symbol
        frames against the *same raw-bytes denominator* -- it answers "what
        fraction of the original signal's bytes did downstream consumers
        receive", so it stays comparable across transports.  (It used to
        divide by ``bytes_in``, which for compressed-in transport is itself
        ~10% of raw -- tiny cadence frames with 4 B headers then pushed the
        ratio past 1.0 even though the service was *reducing* traffic.)
        """
        t = {k: float(v) for k, v in self.totals.items()}
        dt = max(wall_seconds, 1e-9)
        raw_bytes = 4.0 * t["points_in"]
        rep: Dict[str, object] = {
            **t,
            "active": float(self.active_sessions),
            "capacity": float(self.capacity),
            "wall_seconds": wall_seconds,
            "points_per_s": t["points_in"] / dt,
            "symbols_per_s": t["symbols_out"] / dt,
            "ms_per_symbol": 1e3 * dt / max(t["symbols_out"], 1.0),
            "raw_bytes": raw_bytes,
            "wire_in_bytes": t["bytes_in"],
            "wire_in_ratio": t["bytes_in"] / max(raw_bytes, 1.0),
            "wire_out_ratio": t["bytes_out"] / max(raw_bytes, 1.0),
        }
        if self._obs_on:
            rep["obs"] = self.obs.snapshot()
        return rep

    # ------------------------------------------------------------- internals

    def _account_delta(self, sess: _Session, out: dict, labels_row,
                       endpoints_row, n: int, emitted: bool) -> None:
        """Fold one round's symbol delta for one session into its merged
        accumulator + the session/fleet wire-out books (shared by the raw
        and compressed-in ingest paths)."""
        out["labels"].append(labels_row[:n])
        out["endpoints"].append(endpoints_row[:n])
        out["n_new"] += n
        sess.symbols_out += n
        self.totals["symbols_out"] += n
        if emitted:
            frame = DELTA_FRAME_HEADER_BYTES + DELTA_SYMBOL_BYTES * n
            sess.frames_out += 1
            sess.bytes_out += frame
            out["frames"] += 1
            out["bytes"] += frame
            self.totals["frames_out"] += 1
            self.totals["bytes_out"] += frame

    def _grow(self) -> None:
        """Double the slot table (next ladder capacity), carrying all state.

        Runs between batched steps: live slots keep their indices, the new
        upper half is blank.  The next ``_table_step`` call at this capacity
        traces once; steady state at the new size re-donates as before.
        """
        new_cap = self._ladder[self._ladder.index(self.capacity) + 1]
        self._table = self._shard(_concat_slots(
            self._table, self._blanks(new_cap - self.capacity)))
        self._free.extend(range(self.capacity, new_cap))
        self.capacity = new_cap
        self.totals["grows"] += 1
        self.obs.tracer.instant("stream.grow", {"capacity": new_cap})

    def _maybe_shrink(self) -> None:
        """Walk down the ladder once occupancy has stayed at or below a
        quarter of the capacity for ``shrink_patience`` consecutive
        qualifying ticks.

        Two hysteresis mechanisms compose here: the quarter-occupancy bound
        means the shrunken table is at most half full (a single open cannot
        immediately force a re-grow), and the patience counter means a
        session count oscillating across the boundary every tick does not
        re-gather the slot table every tick -- it must *stay* low for
        ``shrink_patience`` observations first.  The walk-down itself is a
        pure permutation of live slots, so delta output is bitwise
        unaffected by when (or whether) it fires.
        """
        if not (self.autoscale and self.capacity > self.min_slots):
            self._low_ticks = 0
            return
        target = self._ladder[self._ladder.index(self.capacity) - 1]
        if len(self._sessions) > target // 2:
            self._low_ticks = 0
            return
        self._low_ticks += 1
        if self._low_ticks < self.shrink_patience:
            return
        self._low_ticks = 0
        while self.autoscale and self.capacity > self.min_slots:
            target = self._ladder[self._ladder.index(self.capacity) - 1]
            if len(self._sessions) > target // 2:
                return
            # compact live slots (ascending, stable) into the low indices,
            # fill the rest from free (blank or stale) slots
            live = sorted(self._sessions.values(), key=lambda s: s.slot)
            perm = [s.slot for s in live]
            perm += [f for f in sorted(self._free)][: target - len(perm)]
            self._table = self._shard(_gather_slots(
                self._table, jnp.asarray(perm, jnp.int32)))
            for new_slot, sess in enumerate(live):
                sess.slot = new_slot
            self._free = list(range(len(live), target))
            self.capacity = target
            self.totals["shrinks"] += 1
            self.obs.tracer.instant("stream.shrink", {"capacity": target})

    def _run_dtw_monitor(self) -> None:
        """Online reconstruction error for every session whose DTW cadence
        fired during this ingest call: DTW(raw so far, pieces so far).

        All due sessions are read out of the slot table in one gather and
        one host transfer (the monitor used to do a per-session
        ``_read_slot`` + unannotated transfer inside the serving loop).
        Jit-compiles per distinct stream length (the reconstruction's output
        shape); the simulated driver keeps lengths small, a production
        monitor would bucket them.
        """
        if not self._dtw_due:
            return
        due = [self._sessions[sid] for sid in sorted(self._dtw_due)
               if sid in self._sessions]
        self._dtw_due.clear()
        if not due:
            return
        t_dtw = time.perf_counter_ns() if self._obs_on else 0
        subs = _gather_slots(
            self._table, jnp.asarray([s.slot for s in due], jnp.int32))
        # one transfer for the whole due set, off the per-round hot path
        subs = jax.device_get(subs)  # sync: ok
        for i, sess in enumerate(due):
            sub = jax.tree.map(lambda leaf: leaf[i], subs)
            raw = np.concatenate(sess.raw)
            lens, incs = pieces_from_wire(
                sub.endpoints, sub.steps, sub.n_pieces, sub.t0)
            rec = reconstruct_from_pieces(
                lens, incs, sub.n_pieces, sub.t0, raw.shape[0])
            d = ops.dtw(raw[None], np.asarray(rec)[None], band=self.dtw_band,
                        force_ref=ops.on_cpu())
            sess.dtw = float(d[0])
        if self._obs_on:
            self.obs.tracer.add("stream.dtw_monitor", t_dtw,
                                {"sessions": len(due)})


# ----------------------------------------------------------------- CLI


def validate_cli_args(ap: argparse.ArgumentParser, args) -> None:
    """Fail fast (exit 2) before any jax work, like the fleet CLI.

    Shared-flag checks live in ``repro.launch.cli.validate_shared_args``;
    only the stream-specific constraints remain here.
    """
    from repro.launch.cli import validate_shared_args

    validate_shared_args(ap, args)
    if args.dtw_every < 0:
        ap.error(f"--dtw-every must be >= 0, got {args.dtw_every}")
    if args.sessions > args.max_slots and not args.evict \
            and args.workload is None:
        ap.error(f"--sessions {args.sessions} exceeds --max-slots "
                 f"{args.max_slots}; pass --evict to allow LRU eviction")
    if args.workload is not None and args.arrival_pattern is not None:
        ap.error("--workload and --arrival-pattern are mutually exclusive")


def _build_workload(args):
    """Resolve the CLI's arrival flags into a ``repro.workload`` trace.

    Precedence: ``--workload FILE.jsonl`` (recorded trace) >
    ``--workload SCENARIO`` (synthesized with the CLI's shape knobs) >
    ``--arrival-pattern`` (deprecated shim) > silent ``roundrobin``.
    """
    from repro.workload import SCENARIOS, Trace, Workload, scenario_seed

    if args.workload is not None and args.workload not in SCENARIOS:
        return Trace.load(args.workload)  # recorded workload_trace/v1 jsonl
    if args.workload is not None:
        wl = Workload(args.workload,
                      seed=scenario_seed(args.workload, args.seed),
                      sessions=args.sessions, length=args.length,
                      window=args.window)
        return wl.trace()
    pattern = args.arrival_pattern
    wl = Workload.from_pattern(
        pattern if pattern is not None else "roundrobin",
        sessions=args.sessions, length=args.length, window=args.window,
        seed=args.seed, _warn=pattern is not None)
    return wl.trace()


def main():
    from repro.launch.cli import (
        add_devices_arg, add_metrics_args, add_slot_table_args,
        add_symed_args)

    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--sessions", type=int, default=6,
                    help="simulated streams arriving at the service")
    ap.add_argument("--length", type=int, default=384)
    ap.add_argument("--window", type=int, default=48,
                    help="arrival window cap (ragged arrivals are padded)")
    ap.add_argument("--workload", default=None, metavar="NAME|FILE",
                    help="arrival trace: a repro.workload scenario name or "
                         "a recorded workload_trace/v1 jsonl "
                         "(default: roundrobin)")
    ap.add_argument("--arrival-pattern", default=None,
                    choices=("roundrobin", "random", "bursty"),
                    help="(deprecated: use --workload) legacy arrival shim")
    ap.add_argument("--dtw-every", type=int, default=0,
                    help="online DTW monitor cadence in windows (0: off)")
    ap.add_argument("--verify", action="store_true",
                    help="check delta concatenation against symed_encode")
    add_slot_table_args(ap, max_slots=4)
    add_devices_arg(
        ap, help="devices in the data mesh; >1 shards the slot table (on "
                 "the CPU platform, also the forced host device count)")
    add_symed_args(ap)
    add_metrics_args(ap)
    args = ap.parse_args()
    validate_cli_args(ap, args)

    from repro.launch.fleet import fleet_data_mesh
    from repro.utils.compile_cache import enable_compile_cache
    from repro.workload.replay import replay_trace

    enable_compile_cache()
    trace = _build_workload(args)
    window_cap = trace.window  # a recorded trace carries its own shape
    cfg = SymEDConfig(tol=args.tol, alpha=args.alpha, n_max=256, k_max=32,
                      len_max=256)
    mesh = fleet_data_mesh(args.devices) if args.devices > 1 else None
    obs = Observability(trace_capacity=65536)
    server = StreamServer(
        cfg, max_sessions=args.max_slots, window_cap=window_cap,
        digitize_every_k=args.digitize_every, dtw_every=args.dtw_every,
        evict_idle=args.evict, autoscale=args.autoscale,
        min_slots=args.min_slots, shrink_patience=args.shrink_patience,
        seed=args.seed, mesh=mesh, pretrace=args.pretrace, obs=obs,
    )
    exporter = None
    if args.metrics_port is not None:
        from repro.obs.export import start_exporter
        exporter = start_exporter(obs, args.metrics_port)
        print(f"metrics exporter        : {exporter.url}/metrics")

    res = replay_trace(trace, cfg=cfg, server=server, verify=args.verify)

    rep = server.report(res.wall_seconds)
    print(f"devices / table shards  : {args.devices}")
    print(f"slot table              : {args.max_slots} slots"
          f"{' (autoscaled)' if args.autoscale else ''}, "
          f"window cap {window_cap}, workload {trace.name}")
    print(f"sessions                : {int(rep['opened'])} opened, "
          f"{int(rep['closed'])} closed, {int(rep['evicted'])} evicted")
    # stable machine-readable summary (CI smoke jobs grep these key=value
    # pairs; keep the keys backward-compatible)
    print("stream_summary "
          f"opened={int(rep['opened'])} closed={int(rep['closed'])} "
          f"evicted={int(rep['evicted'])} capacity={int(rep['capacity'])} "
          f"grows={int(rep['grows'])} shrinks={int(rep['shrinks'])} "
          f"wire_in_bytes={int(rep['wire_in_bytes'])} "
          f"wire_out_bytes={int(rep['bytes_out'])}")
    print(f"wall time               : {rep['wall_seconds']:.2f}s "
          f"({int(rep['steps'])} batched steps)")
    print(f"points in               : {int(rep['points_in'])} "
          f"({int(rep['bytes_in'])} wire-in bytes)")
    print(f"symbols out             : {int(rep['symbols_out'])} in "
          f"{int(rep['frames_out'])} delta frames "
          f"({int(rep['bytes_out'])} wire-out bytes)")
    print(f"symbol latency          : {rep['ms_per_symbol']:.3f} ms/symbol "
          f"(paper: 42ms single-CPU)")
    if args.dtw_every:
        vals = [s["dtw"] for s in res.sessions.values()
                if s["dtw"] is not None]
        if vals:
            print(f"online DTW monitor      : mean {np.mean(vals):.3f} "
                  f"over {len(vals)} sessions")

    if args.verify:
        # the replay engine already ran the bitwise delta-concatenation
        # check against symed_encode (replay_trace(verify=True) raises on
        # any mismatch)
        print(f"delta equivalence       : OK ({res.verified} sessions "
              f"bitwise)")

    # flight-recorder summary (stable key=value line, like stream_summary)
    snap = obs.snapshot()
    lat = snap["histograms"].get("symed_symbol_latency_seconds", {})
    print("obs_summary "
          f"symbol_p50_ms={1e3 * lat.get('p50', 0.0):.3f} "
          f"symbol_p99_ms={1e3 * lat.get('p99', 0.0):.3f} "
          f"symbol_p999_ms={1e3 * lat.get('p999', 0.0):.3f} "
          f"symbols={int(lat.get('count', 0))} "
          f"spans={int(snap['spans_recorded'])}")
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

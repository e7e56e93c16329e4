"""SymED end-to-end pipeline: the paper's contribution as one composable module.

    sender (IoT, Alg. 1)  --one float/piece-->  receiver (edge, Alg. 2+3)

``symed_encode`` runs a single stream through sender -> wire -> receiver and
returns symbols, pieces, centers plus wire-traffic accounting.
``symed_batch`` vmaps it over a fleet slab (the distributed runtime in
``repro.launch.fleet`` shards slabs over the mesh ``data`` axis with
shard_map).

Three ingestion shapes, all bitwise-equal at end-of-stream (tested):

  * **whole-stream** -- ``symed_encode(ts)``: one shot;
  * **chunked sender** -- ``symed_encode_chunk`` windows + ``symed_finish``:
    the sender is online (O(1) carry) but per-step events accumulate until a
    single digitize at the end;
  * **streaming receiver** -- ``symed_step_chunk``/``symed_receive_chunk``
    windows + ``symed_receive_finish``: *both* sides are online.  A
    ``ReceiverState`` carries the compressor, the padded wire buffers, and a
    resumable ``DigitizerState`` across windows; with
    ``digitize_every_k = k`` the digitizer runs over the newly arrived pieces
    every ``k`` windows, so symbols stream out of the receiver while the
    stream is still arriving (the paper's 42ms/symbol deployment shape).
    Total receiver memory is O(n_max), independent of stream length.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.compress import (
    CompressorState, PieceEvent, compress_stream, compressor_finalize,
    compressor_init, compressor_step,
)
from repro.core.digitize import (
    DigitizerState, DigitizeWork, digitize_pieces, digitize_span,
    digitize_span_table,
    digitizer_delta, digitizer_init,
)
from repro.core.metrics import compression_rate_symed, drr, dtw_ref
from repro.core.receiver import (
    append_tail, compact_chunk, compact_events, delta_frame_bytes,
    pieces_from_wire,
)
from repro.core.reconstruct import reconstruct_from_pieces, reconstruct_from_symbols

__all__ = [
    "ReceiverState",
    "SymEDConfig",
    "receiver_init",
    "symed_encode",
    "symed_encode_chunk",
    "symed_finish",
    "symed_step_chunk",
    "symed_receive_chunk",
    "symed_receive_finish",
    "symed_receive_masked_chunk",
    "symed_receive_masked_chunk_table",
    "symed_receive_masked_pieces",
    "symed_receive_masked_pieces_table",
    "symed_batch",
    "symbols_to_string",
]


@dataclasses.dataclass(frozen=True)
class SymEDConfig:
    """Hyperparameters (paper Sec. 4.1 defaults)."""

    tol: float = 0.5          # error-tolerance (compression + digitization)
    alpha: float = 0.01       # damped-window weight (paper: 0.01..0.02)
    scl: float = 1.0          # length-vs-increment weight (2D clustering)
    k_min: int = 3            # minimum alphabet size
    k_max: int = 100          # maximum alphabet size
    len_max: int = 512        # maximum points per piece
    n_max: int = 512          # per-stream piece buffer capacity
    lloyd_iters: int = 10     # Lloyd iterations per k-means warm start

    def static_fields(self) -> Dict[str, Any]:
        return dict(
            len_max=self.len_max, n_max=self.n_max, k_min=self.k_min,
            k_max_active=self.k_max, lloyd_iters=self.lloyd_iters,
        )


def _receive(
    events, key, ts, t_len, n_points, *, tol, scl, n_max, k_min, k_max,
    lloyd_iters, reconstruct
):
    """Wire -> receiver: compact, digitize, score.  Shared by the whole-stream
    (``_encode``) and chunked (``_finish``) paths so their outputs stay
    identical by construction.  ``events`` must carry per-step ``emit`` /
    ``endpoint`` plus the trailing-flush ``tail``; ``t_len`` is the static
    stream length (``ts`` may be just ``ts[:1]`` when not reconstructing).
    ``n_points`` is the same length as a *runtime* scalar: the cr/drr
    divisions must see a runtime divisor, or XLA strength-reduces them to
    reciprocal multiplies and the results drift one ulp from the streaming
    receiver (which divides by the ``t_seen`` carried in its state)."""
    # --- wire ---------------------------------------------------------------
    wire = compact_events(events, n_max=n_max, t0=ts[0])
    # --- receiver (edge node) ----------------------------------------------
    dig = digitize_pieces(
        wire["lengths"], wire["incs"], wire["n_pieces"], key,
        k_cap=k_max, tol=tol, scl=scl, k_min=k_min,
        k_max_active=k_max, lloyd_iters=lloyd_iters,
    )

    out = {
        "symbols": dig["labels"],
        "symbols_online": dig["symbols"],
        "centers": dig["centers"],
        "k": dig["k"],
        "pieces_len": wire["lengths"],
        "pieces_inc": wire["incs"],
        "n_pieces": wire["n_pieces"],
        "wire_bytes": 4.0 + 4.0 * wire["n_pieces"].astype(jnp.float32),
        "cr": compression_rate_symed(wire["n_pieces"], n_points),
        "drr": drr(wire["n_pieces"], n_points),
    }
    if reconstruct:
        rec_p = reconstruct_from_pieces(
            wire["lengths"], wire["incs"], wire["n_pieces"], ts[0], t_len
        )
        rec_s = reconstruct_from_symbols(
            dig["labels"], dig["centers"], wire["n_pieces"], ts[0], t_len
        )
        out["recon_pieces"] = rec_p
        out["recon_symbols"] = rec_s
        out["re_pieces"] = dtw_ref(ts, rec_p)
        out["re_symbols"] = dtw_ref(ts, rec_s)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("len_max", "n_max", "k_min", "k_max", "lloyd_iters", "reconstruct"),
)
def _encode(
    ts, key, n_points, *, tol, alpha, scl, len_max, n_max, k_min, k_max,
    lloyd_iters, reconstruct
):
    ts = jnp.asarray(ts, jnp.float32)

    # --- sender (IoT node) -------------------------------------------------
    events = compress_stream(ts, tol=tol, len_max=len_max, alpha=alpha)
    return _receive(
        events, key, ts, ts.shape[-1], n_points, tol=tol, scl=scl, n_max=n_max,
        k_min=k_min, k_max=k_max, lloyd_iters=lloyd_iters, reconstruct=reconstruct,
    )


def symed_encode(
    ts: jax.Array, cfg: SymEDConfig, key: jax.Array, reconstruct: bool = True
) -> Dict[str, jax.Array]:
    """Encode one stream ``(T,)``; optionally reconstruct + score both modes."""
    ts = jnp.asarray(ts, jnp.float32)
    return _encode(
        ts, key, jnp.asarray(ts.shape[-1], jnp.int32),
        tol=cfg.tol, alpha=cfg.alpha, scl=cfg.scl,
        len_max=cfg.len_max, n_max=cfg.n_max, k_min=cfg.k_min, k_max=cfg.k_max,
        lloyd_iters=cfg.lloyd_iters, reconstruct=reconstruct,
    )


@functools.partial(jax.jit, static_argnames=("len_max", "first"))
def _encode_chunk(chunk, state, *, tol, alpha, len_max, first):  # symlint: entry(drive=chunked, budget=0, shapes=encode-chunk)
    chunk = jnp.asarray(chunk, jnp.float32)
    ts_t = jnp.moveaxis(chunk, -1, 0)
    if first:
        state = compressor_init(ts_t[0])
        xs = ts_t[1:]
    else:
        xs = ts_t

    def step(s, t):
        return compressor_step(s, t, tol=tol, len_max=len_max, alpha=alpha)

    state, events = jax.lax.scan(step, state, xs)
    if first:
        # no-emit slot for t_0 so events align 1:1 with chunk steps
        pad0 = lambda x: jnp.concatenate([jnp.zeros_like(x[:1]), x], axis=0)
        events = PieceEvent(*(pad0(x) for x in events))
    to_batch_last = lambda x: jnp.moveaxis(x, 0, -1)
    ev = {
        "emit": to_batch_last(events.emit),
        "endpoint": to_batch_last(events.endpoint),
        "length": to_batch_last(events.length),
        "inc": to_batch_last(events.inc),
    }
    return state, ev


def symed_encode_chunk(
    ts_chunk: jax.Array, cfg: SymEDConfig, state: CompressorState | None = None
) -> tuple[CompressorState, Dict[str, jax.Array]]:
    """Resumable sender: ingest one ``(..., C)`` window of the stream.

    ``state=None`` opens the stream (the chunk's first point seeds the
    compressor, exactly like ``compress_stream``); pass the returned state to
    ingest the next window.  Step-for-step identical to running
    ``compress_stream`` over the concatenated windows -- this is what makes
    the fleet runtime (``repro.launch.fleet``) *online*: a slab is processed
    in ``chunk_len`` windows with O(1)-per-stream carry instead of one giant
    batch.

    Returns ``(state, events)`` where ``events`` holds per-step ``emit`` /
    ``endpoint`` / ``length`` / ``inc`` arrays shaped like the chunk.
    """
    return _encode_chunk(
        ts_chunk, state, tol=cfg.tol, alpha=cfg.alpha, len_max=cfg.len_max,
        first=state is None,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_max", "k_min", "k_max", "lloyd_iters", "reconstruct"),
)
def _finish(
    events, state, key, ts, n_points, *, tol, scl, n_max, k_min, k_max,
    lloyd_iters, reconstruct
):
    tail = compressor_finalize(state)
    return _receive(
        {**events, "tail": tail}, key, ts, events["emit"].shape[-1], n_points,
        tol=tol, scl=scl, n_max=n_max, k_min=k_min, k_max=k_max,
        lloyd_iters=lloyd_iters, reconstruct=reconstruct,
    )


def symed_finish(
    events: Dict[str, jax.Array],
    state: CompressorState,
    cfg: SymEDConfig,
    key: jax.Array,
    ts: jax.Array,
    reconstruct: bool = True,
) -> Dict[str, jax.Array]:
    """Close a chunked stream: flush the open segment, wire-compact, digitize.

    ``events`` are the per-step arrays from ``symed_encode_chunk`` calls,
    concatenated along the step axis (single stream, ``(T,)``); ``ts`` is the
    full raw stream (the reconstruction error is scored against it; only
    ``ts[0]`` enters the wire).  Output dict matches ``symed_encode``.
    """
    return _finish(
        events, state, key, jnp.asarray(ts, jnp.float32),
        jnp.asarray(events["emit"].shape[-1], jnp.int32),
        tol=cfg.tol, scl=cfg.scl, n_max=cfg.n_max, k_min=cfg.k_min,
        k_max=cfg.k_max, lloyd_iters=cfg.lloyd_iters, reconstruct=reconstruct,
    )


class ReceiverState(NamedTuple):
    """Full online SymED state for one stream: sender + wire + receiver.

    ``comp`` is the O(1) sender carry; ``endpoints``/``steps``/``n_pieces``
    are the receiver's padded wire-compaction buffers (what arrived, and
    when); ``dig`` is the resumable digitizer (``dig.n`` pieces of the buffer
    have been digitized so far); ``symbols_online`` accumulates the symbol
    emitted when each piece was first digitized.  ``t0``/``t_seen``/``chunks``
    anchor the wire ("hello" payload, global step clock, cadence counter).
    """

    comp: CompressorState
    dig: DigitizerState
    endpoints: jax.Array       # (n_max,) f32 transmitted endpoints
    steps: jax.Array           # (n_max,) i32 arrival step per piece
    n_pieces: jax.Array        # () i32 pieces compacted so far
    symbols_online: jax.Array  # (n_max,) i32 symbol at first digitization
    t0: jax.Array              # () f32 first raw point (the "hello")
    t_seen: jax.Array          # () i32 stream points ingested so far
    chunks: jax.Array          # () i32 windows ingested so far


def receiver_init(cfg: SymEDConfig, key: jax.Array) -> ReceiverState:
    """Blank (unseeded) receiver slot for session tables.

    ``t_seen == 0`` marks the slot as not yet opened by a stream point: the
    first valid point of the first ``symed_receive_masked_chunk`` window
    seeds the compressor exactly like ``symed_receive_chunk(state=None)``
    does with ``chunk[0]``.  ``repro.launch.stream`` vmaps this over the
    slot axis to build its resident session table.
    """
    return ReceiverState(
        comp=compressor_init(jnp.zeros((), jnp.float32)),
        dig=digitizer_init(cfg.n_max, cfg.k_max, key),
        endpoints=jnp.zeros((cfg.n_max,), jnp.float32),
        steps=jnp.zeros((cfg.n_max,), jnp.int32),
        n_pieces=jnp.zeros((), jnp.int32),
        symbols_online=jnp.zeros((cfg.n_max,), jnp.int32),
        t0=jnp.zeros((), jnp.float32),
        t_seen=jnp.zeros((), jnp.int32),
        chunks=jnp.zeros((), jnp.int32),
    )


def _digitize_new_pieces(
    dig, symbols_online, endpoints, steps, n_pieces, t0, *, tol, scl, n_max,
    k_min, k_max, lloyd_iters
):
    """Digitize buffer slots ``[dig.n, n_pieces)``; record first-time symbols.

    Returns ``(dig, symbols_online, work)`` (``work``: the pass's
    ``DigitizeWork``)."""
    lens, incs = pieces_from_wire(endpoints, steps, n_pieces, t0)
    dig_new, span_syms, work = digitize_span(
        dig, lens, incs, dig.n, n_pieces, tol=tol, scl=scl,
        k_min=k_min, k_max_active=k_max, lloyd_iters=lloyd_iters,
    )
    idx = jnp.arange(n_max)
    in_span = (idx >= dig.n) & (idx < n_pieces)
    return dig_new, jnp.where(in_span, span_syms, symbols_online), work


def _digitize_on_cadence(emitted, dig, symbols_online, digitize):
    """``lax.cond(emitted, digitize, skip)`` for one slot: a skipped window
    leaves the digitizer as it was and did no loop work."""
    def skip(dig, symbols_online):
        return dig, symbols_online, DigitizeWork.zeros()

    return jax.lax.cond(emitted, digitize, skip, dig, symbols_online)


def _digitize_new_pieces_table(
    dig, symbols_online, endpoints, steps, n_pieces, t0, emitted, *, tol, scl,
    n_max, k_min, k_max, lloyd_iters, use_kernel
):
    """Table-level ``_digitize_new_pieces``: one fused pass over all slots.

    ``emitted`` (S,) gates the digitize per lane *by span*, not by branch:
    off-cadence lanes get an empty ``[dig.n, dig.n)`` span, which the
    ``digitize_span_table`` cursor loop never visits -- bitwise-identical to
    the per-slot ``lax.cond(emitted, digitize, skip)`` (whose vmapped select
    would run the full clustering for every lane and discard it).
    """
    lens, incs = jax.vmap(pieces_from_wire)(endpoints, steps, n_pieces, t0)
    hi = jnp.where(emitted, n_pieces, dig.n)
    dig_new, span_syms, work = digitize_span_table(
        dig, lens, incs, dig.n, hi, tol=tol, scl=scl,
        k_min=k_min, k_max_active=k_max, lloyd_iters=lloyd_iters,
        use_kernel=use_kernel,
    )
    idx = jnp.arange(n_max)[None, :]
    in_span = (idx >= dig.n[:, None]) & (idx < hi[:, None])
    return dig_new, jnp.where(in_span, span_syms, symbols_online), work


def _symbol_delta_info(n_dig_prev, dig, symbols_online, endpoints, emitted):
    """The per-chunk wire-out payload: what this call's digitize pass added.

    ``emitted`` flags whether a delta frame goes on the wire at all (off-
    cadence windows emit nothing); ``frame_bytes`` is the outbound traffic
    of the frame (0 when no frame is emitted).
    """
    labels_d, endpoints_d, n_new = digitizer_delta(
        n_dig_prev, dig, symbols_online, endpoints
    )
    emitted = jnp.asarray(emitted, bool)
    return {
        "labels": labels_d,
        "endpoints": endpoints_d,
        "n_new": n_new,
        "emitted": emitted,
        "frame_bytes": jnp.where(emitted, delta_frame_bytes(n_new), 0.0),
    }


@functools.partial(
    jax.jit,
    static_argnames=(
        "len_max", "n_max", "k_min", "k_max", "lloyd_iters",
        "digitize_every_k", "first",
    ),
)
def _receive_chunk(  # symlint: entry(drive=chunked, budget=0, shapes=receive-chunk)
    chunk, state, key, *, tol, alpha, scl, len_max, n_max, k_min, k_max,
    lloyd_iters, digitize_every_k, first,
):
    chunk = jnp.asarray(chunk, jnp.float32)
    if first:
        state = ReceiverState(
            comp=compressor_init(chunk[0]),
            dig=digitizer_init(n_max, k_max, key),
            endpoints=jnp.zeros((n_max,), jnp.float32),
            steps=jnp.zeros((n_max,), jnp.int32),
            n_pieces=jnp.zeros((), jnp.int32),
            symbols_online=jnp.zeros((n_max,), jnp.int32),
            t0=chunk[0],
            t_seen=jnp.ones((), jnp.int32),
            chunks=jnp.zeros((), jnp.int32),
        )
        xs = chunk[1:]
    else:
        xs = chunk

    # --- sender: same scan step as compress_stream / symed_encode_chunk ----
    def step(s, t):
        return compressor_step(s, t, tol=tol, len_max=len_max, alpha=alpha)

    comp, events = jax.lax.scan(step, state.comp, xs)

    # --- wire: scatter this window's emissions into the padded buffers -----
    step_idx = state.t_seen + jnp.arange(xs.shape[0], dtype=jnp.int32)
    endpoints, steps, n_pieces = compact_chunk(
        state.endpoints, state.steps, state.n_pieces,
        events.emit, events.endpoint, step_idx,
    )
    t_seen = state.t_seen + xs.shape[0]
    chunks = state.chunks + 1

    # --- receiver: digitize the newly arrived pieces every k windows -------
    n_dig_prev = state.dig.n
    if digitize_every_k:
        def digitize(dig, symbols_online):
            return _digitize_new_pieces(
                dig, symbols_online, endpoints, steps, n_pieces, state.t0,
                tol=tol, scl=scl, n_max=n_max, k_min=k_min, k_max=k_max,
                lloyd_iters=lloyd_iters,
            )

        emitted = chunks % digitize_every_k == 0
        dig, symbols_online, work = _digitize_on_cadence(
            emitted, state.dig, state.symbols_online, digitize)
    else:
        emitted = jnp.zeros((), bool)
        dig, symbols_online, work = (state.dig, state.symbols_online,
                                     DigitizeWork.zeros())

    new_state = ReceiverState(
        comp=comp, dig=dig, endpoints=endpoints, steps=steps,
        n_pieces=n_pieces, symbols_online=symbols_online,
        t0=state.t0, t_seen=t_seen, chunks=chunks,
    )
    info = {
        "n_pieces": n_pieces,
        "n_digitized": dig.n,
        "symbols_online": symbols_online,
        "symbol_delta": _symbol_delta_info(
            n_dig_prev, dig, symbols_online, endpoints, emitted
        ),
        "work": work,
    }
    return new_state, info


def symed_receive_chunk(
    ts_chunk: jax.Array,
    cfg: SymEDConfig,
    state: Optional[ReceiverState] = None,
    key: Optional[jax.Array] = None,
    *,
    digitize_every_k: int = 1,
) -> Tuple[ReceiverState, Dict[str, jax.Array]]:
    """Fully-online step: ingest one ``(C,)`` window, sender *and* receiver.

    ``state=None`` opens the stream (``key`` is then required -- it seeds the
    digitizer exactly like the ``symed_finish`` path).  Every call compresses
    the window and wire-compacts the emitted pieces; every
    ``digitize_every_k``-th call additionally digitizes the pieces that
    arrived since the last digitization, so symbols stream out while the
    stream is still arriving.  ``digitize_every_k=0`` defers all digitization
    to ``symed_receive_finish`` (the pure ``symed_step_chunk`` behavior).

    End-of-stream outputs (via ``symed_receive_finish``) are bitwise-equal to
    ``symed_encode`` / ``symed_finish`` on the same stream for *any* window
    split and cadence -- the digitizer state evolution depends only on the
    piece arrival order, never on when it runs (tested in
    ``tests/test_streaming_receiver.py``).

    Returns ``(state, info)``: ``info["n_pieces"]`` pieces arrived so far, of
    which ``info["n_digitized"]`` have symbols in ``info["symbols_online"]``.
    ``info["symbol_delta"]`` is the per-chunk wire-out payload -- the
    ``(labels, endpoints, n_new)`` symbols this call's digitize pass added
    (``emitted``/``frame_bytes`` describe the outbound frame; concatenating
    the deltas of every call plus the finish reproduces ``symbols_online``
    exactly -- see ``repro.launch.stream``).  ``info["work"]`` counts the
    digitize loop's trips and k-growth rounds (``DigitizeWork``).

    Single-stream semantics ((C,) windows); ``jax.vmap`` over the leading
    axis for slabs (``repro.launch.fleet`` does exactly that).
    """
    if state is None and key is None:
        raise ValueError("opening a stream (state=None) requires a PRNG key")
    if digitize_every_k < 0:
        raise ValueError(f"digitize_every_k must be >= 0, got {digitize_every_k}")
    if key is None:
        key = jax.random.key(0)  # ignored when state is not None
    return _receive_chunk(
        ts_chunk, state, key, tol=cfg.tol, alpha=cfg.alpha, scl=cfg.scl,
        len_max=cfg.len_max, n_max=cfg.n_max, k_min=cfg.k_min, k_max=cfg.k_max,
        lloyd_iters=cfg.lloyd_iters, digitize_every_k=int(digitize_every_k),
        first=state is None,
    )


def _masked_sender_wire(chunk, n_valid, state, *, tol, alpha, len_max):
    """Per-slot sender scan + wire compaction of one masked window.

    The non-digitize half of ``_masked_receive_chunk``, factored out so the
    table-level path (``symed_receive_masked_chunk_table``) can vmap it
    while hoisting the digitize pass out of the per-slot program.  Returns
    ``(comp, t0, t_seen, endpoints, steps, n_pieces, chunks)``.

    Three runtime branches per scan slot (vs the static ``first`` split of
    ``_receive_chunk``): padding passes the carry through, the stream's
    very first valid point seeds the compressor (compressor_init, exactly
    like ``chunk[0]`` in the unmasked path), everything else runs
    ``compressor_step``.  Per-lane arithmetic is identical to the unmasked
    path, so end-of-stream outputs stay bitwise-equal.
    """
    chunk = jnp.asarray(chunk, jnp.float32)
    c_len = chunk.shape[0]

    def no_event():
        return (
            jnp.zeros((), bool), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.int32),
        )

    def step(carry, inp):
        comp, t0, t_seen = carry
        x, valid = inp

        def skip(comp, t0, t_seen):
            return (comp, t0, t_seen), no_event()

        def seed(comp, t0, t_seen):
            return (compressor_init(x), x, jnp.ones((), jnp.int32)), no_event()

        def ingest(comp, t0, t_seen):
            comp2, ev = compressor_step(
                comp, x, tol=tol, len_max=len_max, alpha=alpha
            )
            # t_seen is the 0-based stream index of x: the receiver's
            # arrival clock, same convention as the unmasked ``step_idx``
            return (comp2, t0, t_seen + 1), (ev.emit, ev.endpoint, t_seen)

        branch = jnp.where(valid, jnp.where(t_seen == 0, 1, 2), 0)
        return jax.lax.switch(branch, [skip, seed, ingest], comp, t0, t_seen)

    valid = jnp.arange(c_len) < n_valid
    (comp, t0, t_seen), (emit, chunk_endpoints, step_idx) = jax.lax.scan(
        step, (state.comp, state.t0, state.t_seen), (chunk, valid)
    )

    endpoints, steps, n_pieces = compact_chunk(
        state.endpoints, state.steps, state.n_pieces,
        emit, chunk_endpoints, step_idx,
    )
    chunks = state.chunks + (n_valid > 0).astype(jnp.int32)
    return comp, t0, t_seen, endpoints, steps, n_pieces, chunks


@functools.partial(
    jax.jit,
    static_argnames=(
        "len_max", "n_max", "k_min", "k_max", "lloyd_iters", "digitize_every_k",
    ),
)
def _masked_receive_chunk(
    chunk, n_valid, state, *, tol, alpha, scl, len_max, n_max, k_min, k_max,
    lloyd_iters, digitize_every_k,
):
    # --- sender + wire: scan every padded slot; only the first n_valid act -
    comp, t0, t_seen, endpoints, steps, n_pieces, chunks = _masked_sender_wire(
        chunk, n_valid, state, tol=tol, alpha=alpha, len_max=len_max
    )

    n_dig_prev = state.dig.n
    if digitize_every_k:
        def digitize(dig, symbols_online):
            return _digitize_new_pieces(
                dig, symbols_online, endpoints, steps, n_pieces, t0,
                tol=tol, scl=scl, n_max=n_max, k_min=k_min, k_max=k_max,
                lloyd_iters=lloyd_iters,
            )

        emitted = (n_valid > 0) & (chunks % digitize_every_k == 0)
        dig, symbols_online, work = _digitize_on_cadence(
            emitted, state.dig, state.symbols_online, digitize)
    else:
        emitted = jnp.zeros((), bool)
        dig, symbols_online, work = (state.dig, state.symbols_online,
                                     DigitizeWork.zeros())

    new_state = ReceiverState(
        comp=comp, dig=dig, endpoints=endpoints, steps=steps,
        n_pieces=n_pieces, symbols_online=symbols_online,
        t0=t0, t_seen=t_seen, chunks=chunks,
    )
    info = {
        "n_pieces": n_pieces,
        "n_digitized": dig.n,
        "t_seen": t_seen,
        "symbols_online": symbols_online,
        "symbol_delta": _symbol_delta_info(
            n_dig_prev, dig, symbols_online, endpoints, emitted
        ),
        "work": work,
    }
    return new_state, info


def symed_receive_masked_chunk(  # symlint: entry(pair=chunk/slot, shapes=pair-chunk-slot)
    ts_chunk: jax.Array,
    n_valid: jax.Array,
    cfg: SymEDConfig,
    state: ReceiverState,
    *,
    digitize_every_k: int = 1,
) -> Tuple[ReceiverState, Dict[str, jax.Array]]:
    """Session-table variant of ``symed_receive_chunk``: padded ragged ingest.

    Ingests the first ``n_valid`` points of the ``(C,)`` window ``ts_chunk``
    (a *runtime* scalar -- network arrivals are ragged) into a state that
    must already exist (``receiver_init`` for a fresh slot; seeding happens
    at runtime when the first valid point arrives, so fresh and resumed
    slots batch through one program).  ``n_valid = 0`` is a no-op carrying
    the state through unchanged -- idle slots of a session table cost one
    masked scan, no state change.

    Bitwise contract: for any padding arrangement, the resulting state
    equals what ``symed_receive_chunk`` produces on the same valid points,
    so end-of-stream outputs stay bitwise-equal to ``symed_encode`` /
    ``symed_finish`` (tested in ``tests/test_stream_service.py``).

    Single-slot semantics; ``jax.vmap`` over the leading axis for slot
    tables (``repro.launch.stream`` does exactly that, under a donated jit).
    """
    if digitize_every_k < 0:
        raise ValueError(f"digitize_every_k must be >= 0, got {digitize_every_k}")
    return _masked_receive_chunk(
        ts_chunk, jnp.asarray(n_valid, jnp.int32), state,
        tol=cfg.tol, alpha=cfg.alpha, scl=cfg.scl, len_max=cfg.len_max,
        n_max=cfg.n_max, k_min=cfg.k_min, k_max=cfg.k_max,
        lloyd_iters=cfg.lloyd_iters, digitize_every_k=int(digitize_every_k),
    )


def symed_receive_masked_chunk_table(  # symlint: entry(pair=chunk/table, shapes=pair-chunk-table)
    windows: jax.Array,
    n_valid: jax.Array,
    cfg: SymEDConfig,
    table: ReceiverState,
    *,
    digitize_every_k: int = 1,
    use_kernel: bool = False,
) -> Tuple[ReceiverState, Dict[str, jax.Array]]:
    """Slot-table batch of ``symed_receive_masked_chunk`` with fused digitize.

    The sender scan + wire compaction run per slot under ``jax.vmap``
    (identical lowering to vmapping the per-slot function); the digitize
    pass is hoisted to *table level* -- one ``digitize_span_table`` cursor
    loop whose trip count is the widest span of newly arrived pieces in the
    table (the per-slot path pays O(n_max) per lane under vmap's
    cond-to-select lowering), and whose Lloyd assign half-steps fuse across
    all slots into single ``pallas_call``s when ``use_kernel=True``
    (``kernels.ops.kmeans_assign``; CPU deployments keep the bitwise
    vmapped reference path).

    Args:
      windows: (S, C) padded arrival windows.
      n_valid: (S,) valid point counts (0 = idle slot, masked no-op).
      table: batched ReceiverState ((S,) leading axis on every leaf).

    Returns ``(table, info)`` shaped like a vmapped
    ``symed_receive_masked_chunk`` -- and, on the reference path, bitwise-
    equal to it (property battery in ``tests/test_stream_service.py``),
    except ``info["work"].rounds_run``: every lane carries the k-growth
    rounds the table's shared loop ran, where a slot counts its own.
    Callers jit this (``repro.launch.stream._table_step`` donates the table
    through it); it is not jitted here.
    """
    if digitize_every_k < 0:
        raise ValueError(f"digitize_every_k must be >= 0, got {digitize_every_k}")
    n_valid = jnp.asarray(n_valid, jnp.int32)
    comp, t0, t_seen, endpoints, steps, n_pieces, chunks = jax.vmap(
        lambda w, n, s: _masked_sender_wire(
            w, n, s, tol=cfg.tol, alpha=cfg.alpha, len_max=cfg.len_max)
    )(windows, n_valid, table)

    n_dig_prev = table.dig.n
    if digitize_every_k:
        emitted = (n_valid > 0) & (chunks % int(digitize_every_k) == 0)
        dig, symbols_online, work = _digitize_new_pieces_table(
            table.dig, table.symbols_online, endpoints, steps, n_pieces, t0,
            emitted, tol=cfg.tol, scl=cfg.scl, n_max=cfg.n_max,
            k_min=cfg.k_min, k_max=cfg.k_max, lloyd_iters=cfg.lloyd_iters,
            use_kernel=use_kernel,
        )
    else:
        emitted = jnp.zeros(n_valid.shape, bool)
        dig, symbols_online, work = (table.dig, table.symbols_online,
                                     DigitizeWork.zeros(n_valid.shape))

    new_table = ReceiverState(
        comp=comp, dig=dig, endpoints=endpoints, steps=steps,
        n_pieces=n_pieces, symbols_online=symbols_online,
        t0=t0, t_seen=t_seen, chunks=chunks,
    )
    info = {
        "n_pieces": n_pieces,
        "n_digitized": dig.n,
        "t_seen": t_seen,
        "symbols_online": symbols_online,
        "symbol_delta": jax.vmap(_symbol_delta_info)(
            n_dig_prev, dig, symbols_online, endpoints, emitted
        ),
        "work": work,
    }
    return new_table, info


def symed_receive_masked_pieces_table(  # symlint: entry(pair=pieces/table, shapes=pair-pieces-table)
    piece_endpoints: jax.Array,
    piece_steps: jax.Array,
    n_valid: jax.Array,
    hello: jax.Array,
    t_seen: jax.Array,
    cfg: SymEDConfig,
    table: ReceiverState,
    *,
    digitize_every_k: int = 1,
    use_kernel: bool = False,
) -> Tuple[ReceiverState, Dict[str, jax.Array]]:
    """Compressed-in counterpart of ``symed_receive_masked_chunk_table``.

    Scatters each slot's padded piece tuples into its wire buffers (vmapped
    ``compact_chunk``; the sender already ran the compressor) and digitizes
    at table level.  See ``symed_receive_masked_pieces`` for the wire
    semantics and ``symed_receive_masked_chunk_table`` for the fusion /
    bitwise contract.  Arguments carry an (S,) slot axis.
    """
    if digitize_every_k < 0:
        raise ValueError(f"digitize_every_k must be >= 0, got {digitize_every_k}")
    n_valid = jnp.asarray(n_valid, jnp.int32)
    p_cap = piece_endpoints.shape[1]
    t0 = jnp.where(table.t_seen == 0, jnp.asarray(hello, jnp.float32), table.t0)
    valid = jnp.arange(p_cap)[None, :] < n_valid[:, None]
    endpoints, steps, n_pieces = jax.vmap(compact_chunk)(
        table.endpoints, table.steps, table.n_pieces,
        valid, jnp.asarray(piece_endpoints, jnp.float32),
        jnp.asarray(piece_steps, jnp.int32),
    )
    t_seen = jnp.maximum(table.t_seen, jnp.asarray(t_seen, jnp.int32))
    chunks = table.chunks + (n_valid > 0).astype(jnp.int32)

    n_dig_prev = table.dig.n
    if digitize_every_k:
        emitted = (n_valid > 0) & (chunks % int(digitize_every_k) == 0)
        dig, symbols_online, work = _digitize_new_pieces_table(
            table.dig, table.symbols_online, endpoints, steps, n_pieces, t0,
            emitted, tol=cfg.tol, scl=cfg.scl, n_max=cfg.n_max,
            k_min=cfg.k_min, k_max=cfg.k_max, lloyd_iters=cfg.lloyd_iters,
            use_kernel=use_kernel,
        )
    else:
        emitted = jnp.zeros(n_valid.shape, bool)
        dig, symbols_online, work = (table.dig, table.symbols_online,
                                     DigitizeWork.zeros(n_valid.shape))

    new_table = ReceiverState(
        comp=table.comp, dig=dig, endpoints=endpoints, steps=steps,
        n_pieces=n_pieces, symbols_online=symbols_online,
        t0=t0, t_seen=t_seen, chunks=chunks,
    )
    info = {
        "n_pieces": n_pieces,
        "n_digitized": dig.n,
        "t_seen": t_seen,
        "symbols_online": symbols_online,
        "symbol_delta": jax.vmap(_symbol_delta_info)(
            n_dig_prev, dig, symbols_online, endpoints, emitted
        ),
        "work": work,
    }
    return new_table, info


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_max", "k_min", "k_max", "lloyd_iters", "digitize_every_k",
    ),
)
def _masked_receive_pieces(
    piece_endpoints, piece_steps, n_valid, hello, t_seen_new, state, *, tol,
    scl, n_max, k_min, k_max, lloyd_iters, digitize_every_k,
):
    p_cap = piece_endpoints.shape[0]

    # --- wire: the sender already ran the compressor; just scatter ---------
    # ``compact_chunk`` with a prefix mask places the arriving tuples at
    # slots [n_pieces, n_pieces + n_valid) -- the identical buffer content a
    # raw-mode ingest of the same stream would have produced, which is what
    # keeps the end-of-stream outputs bitwise-equal across transport modes.
    t0 = jnp.where(state.t_seen == 0, hello, state.t0)
    valid = jnp.arange(p_cap) < n_valid
    endpoints, steps, n_pieces = compact_chunk(
        state.endpoints, state.steps, state.n_pieces,
        valid, jnp.asarray(piece_endpoints, jnp.float32),
        jnp.asarray(piece_steps, jnp.int32),
    )
    t_seen = jnp.maximum(state.t_seen, t_seen_new)
    chunks = state.chunks + (n_valid > 0).astype(jnp.int32)

    # --- receiver: digitize cadence identical to the masked raw path ------
    n_dig_prev = state.dig.n
    if digitize_every_k:
        def digitize(dig, symbols_online):
            return _digitize_new_pieces(
                dig, symbols_online, endpoints, steps, n_pieces, t0,
                tol=tol, scl=scl, n_max=n_max, k_min=k_min, k_max=k_max,
                lloyd_iters=lloyd_iters,
            )

        emitted = (n_valid > 0) & (chunks % digitize_every_k == 0)
        dig, symbols_online, work = _digitize_on_cadence(
            emitted, state.dig, state.symbols_online, digitize)
    else:
        emitted = jnp.zeros((), bool)
        dig, symbols_online, work = (state.dig, state.symbols_online,
                                     DigitizeWork.zeros())

    new_state = ReceiverState(
        comp=state.comp, dig=dig, endpoints=endpoints, steps=steps,
        n_pieces=n_pieces, symbols_online=symbols_online,
        t0=t0, t_seen=t_seen, chunks=chunks,
    )
    info = {
        "n_pieces": n_pieces,
        "n_digitized": dig.n,
        "t_seen": t_seen,
        "symbols_online": symbols_online,
        "symbol_delta": _symbol_delta_info(
            n_dig_prev, dig, symbols_online, endpoints, emitted
        ),
        "work": work,
    }
    return new_state, info


def symed_receive_masked_pieces(  # symlint: entry(pair=pieces/slot, shapes=pair-pieces-slot)
    piece_endpoints: jax.Array,
    piece_steps: jax.Array,
    n_valid: jax.Array,
    hello: jax.Array,
    t_seen: jax.Array,
    cfg: SymEDConfig,
    state: ReceiverState,
    *,
    digitize_every_k: int = 1,
) -> Tuple[ReceiverState, Dict[str, jax.Array]]:
    """Compressed-in variant of ``symed_receive_masked_chunk``.

    The sender ran ``CompressorState`` locally (``repro.launch.transport``
    pieces mode) and ships finished pieces instead of raw points: the first
    ``n_valid`` of the padded ``(P,)`` tuples ``(piece_endpoints[i],
    piece_steps[i])`` are scattered straight into the wire buffers -- the
    per-slot compressor never runs.  ``hello`` is the sender's 4-byte t0
    payload (consumed only while ``state.t_seen == 0``); ``t_seen`` is the
    sender's cumulative point clock after this frame (runtime scalar; the
    receiver needs it for cr/drr and as the close-time arrival clock).
    ``n_valid = 0`` with ``t_seen > 0`` still advances the clock (a frame
    whose window finished no piece).

    Bitwise contract: scattering the tuples a sender-side
    ``symed_encode_chunk`` emitted (via ``compress_stream``'s arithmetic --
    the same per-point program the raw-mode receiver runs) yields the exact
    wire-buffer content of raw-mode ingest, and the digitizer evolution
    depends only on piece arrival order, so ``symed_receive_finish`` outputs
    and concatenated symbol deltas stay bitwise-equal to ``symed_encode``
    across transport modes (tested in ``tests/test_transport.py``).

    The sender's trailing flush arrives as an ordinary piece tuple with
    ``step = t_seen`` (the CLOSE frame's payload); the blank slot compressor
    then has nothing to flush at ``symed_receive_finish``.

    Single-slot semantics; ``jax.vmap`` over the leading axis for slot
    tables (``repro.launch.stream.ingest_pieces_many`` does exactly that).
    """
    if digitize_every_k < 0:
        raise ValueError(f"digitize_every_k must be >= 0, got {digitize_every_k}")
    return _masked_receive_pieces(
        piece_endpoints, piece_steps, jnp.asarray(n_valid, jnp.int32),
        jnp.asarray(hello, jnp.float32), jnp.asarray(t_seen, jnp.int32),
        state, tol=cfg.tol, scl=cfg.scl, n_max=cfg.n_max, k_min=cfg.k_min,
        k_max=cfg.k_max, lloyd_iters=cfg.lloyd_iters,
        digitize_every_k=int(digitize_every_k),
    )


def symed_step_chunk(
    ts_chunk: jax.Array,
    cfg: SymEDConfig,
    state: Optional[ReceiverState] = None,
    key: Optional[jax.Array] = None,
) -> Tuple[ReceiverState, Dict[str, jax.Array]]:
    """Sender+wire only: ingest a window without running the digitizer.

    Equivalent to ``symed_receive_chunk(..., digitize_every_k=0)``; the
    digitizer catches up wholesale in ``symed_receive_finish``.
    """
    return symed_receive_chunk(ts_chunk, cfg, state, key, digitize_every_k=0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_max", "k_min", "k_max", "lloyd_iters", "reconstruct", "with_delta",
    ),
)
def _receive_finish(  # symlint: entry(drive=chunked, budget=0, shapes=receive-finish)
    state, ts, *, tol, scl, n_max, k_min, k_max, lloyd_iters, reconstruct,
    with_delta=False,
):
    tail = compressor_finalize(state.comp)
    endpoints, steps, n_pieces = append_tail(
        state.endpoints, state.steps, state.n_pieces, tail, state.t_seen
    )
    lens, incs = pieces_from_wire(endpoints, steps, n_pieces, state.t0)
    dig, span_syms, _ = digitize_span(
        state.dig, lens, incs, state.dig.n, n_pieces, tol=tol, scl=scl,
        k_min=k_min, k_max_active=k_max, lloyd_iters=lloyd_iters,
    )
    idx = jnp.arange(n_max)
    in_span = (idx >= state.dig.n) & (idx < n_pieces)
    symbols_online = jnp.where(in_span, span_syms, state.symbols_online)

    out = {
        "symbols": dig.labels,
        "symbols_online": symbols_online,
        "centers": dig.centers,
        "k": dig.k,
        "pieces_len": lens,
        "pieces_inc": incs,
        "n_pieces": n_pieces,
        "wire_bytes": 4.0 + 4.0 * n_pieces.astype(jnp.float32),
        "cr": compression_rate_symed(n_pieces, state.t_seen),
        "drr": drr(n_pieces, state.t_seen),
    }
    if with_delta:
        # the closing delta frame: every piece digitized by this flush
        out["symbol_delta"] = _symbol_delta_info(
            state.dig.n, dig, symbols_online, endpoints,
            jnp.ones((), bool),
        )
    if reconstruct:
        t_len = ts.shape[-1]
        rec_p = reconstruct_from_pieces(lens, incs, n_pieces, state.t0, t_len)
        rec_s = reconstruct_from_symbols(
            dig.labels, dig.centers, n_pieces, state.t0, t_len
        )
        out["recon_pieces"] = rec_p
        out["recon_symbols"] = rec_s
        out["re_pieces"] = dtw_ref(ts, rec_p)
        out["re_symbols"] = dtw_ref(ts, rec_s)
    return out


def symed_receive_finish(
    state: ReceiverState,
    cfg: SymEDConfig,
    ts: Optional[jax.Array] = None,
    reconstruct: bool = False,
    *,
    with_delta: bool = False,
) -> Dict[str, jax.Array]:
    """Close a streaming-receiver stream: flush the tail, digitize the rest.

    Output dict matches ``symed_encode`` / ``symed_finish`` bitwise.  ``ts``
    (the full raw stream) is only required when ``reconstruct=True`` -- unlike
    ``symed_finish``, the receiver carries everything else (``t0``, the
    stream length ``t_seen``) in its state.  ``with_delta=True`` additionally
    returns ``out["symbol_delta"]`` -- the closing wire-out frame carrying
    the symbols this final digitize pass added (the last piece of the
    delta-concatenation contract; see ``repro.launch.stream``).
    """
    if reconstruct and ts is None:
        raise ValueError("reconstruct=True requires the raw stream ts")
    ts = jnp.zeros((1,), jnp.float32) if ts is None else jnp.asarray(ts, jnp.float32)
    return _receive_finish(
        state, ts, tol=cfg.tol, scl=cfg.scl, n_max=cfg.n_max, k_min=cfg.k_min,
        k_max=cfg.k_max, lloyd_iters=cfg.lloyd_iters, reconstruct=reconstruct,
        with_delta=with_delta,
    )


def symed_batch(
    ts: jax.Array, cfg: SymEDConfig, key: jax.Array, reconstruct: bool = True
) -> Dict[str, jax.Array]:
    """Vectorized fleet slab: ``ts`` is (B, T); one PRNG key per stream."""
    keys = jax.random.split(key, ts.shape[0])
    return jax.vmap(lambda t, k: symed_encode(t, cfg, k, reconstruct))(ts, keys)


def symbols_to_string(labels, n_pieces) -> str:
    """Host-side helper: int labels -> 'abc...' string (I/O boundary only)."""
    import numpy as np

    labels = np.asarray(labels)[: int(n_pieces)]
    alphabet = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
    return "".join(alphabet[l % len(alphabet)] for l in labels)

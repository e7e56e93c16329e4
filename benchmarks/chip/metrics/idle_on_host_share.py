"""Share of the traced window in which the device idled while the host was
not waiting for traffic, in %: the idle a faster or overlapped host path
could remove.

The idle intervals are those ``device_idle_share`` counts: the gaps
between the device's ops, moved onto the recorder's clock by the profiler's
table-step annotations (``Context._clock_offset``), and the window's two
edges.  The trace keeps no op times at the edges, only their sum (the
window less the ops' span); the leading edge runs from the window's start
to the first table step dispatched in it, unless a device program (a step
from its dispatch to its harvest, or a session's close) was already in
flight then, and the trailing edge is the rest.  From these the program's
``transport.wait`` spans (the serve loop in ``select`` with nothing to
read) are taken out.  Never above ``device_idle_share``.  ``None`` where
the program records no ``transport.wait`` span, or nothing aligns the
clocks.
"""


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(a, b, merged):
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)


def _edges(ctx, spans, edge_s):
    """(leading, trailing) idle at the window's edges, ``edge_s`` in all."""
    w0, w1 = ctx.window
    disp = sorted(t0 for n, t0, _ in spans if n.startswith("stream.dispatch"))
    harv = sorted(t1 for n, _, t1 in spans if n.startswith("stream.harvest"))
    busy = list(zip(disp, harv))
    busy += [(t0, t1) for n, t0, t1 in spans if n == "stream.close"]
    if any(t0 <= w0 < t1 for t0, t1 in busy):
        lead = 0.0
    else:
        starts = [t0 for t0, _ in busy if t0 >= w0]
        lead = min(edge_s, min(starts) - w0) if starts else edge_s
    return lead, edge_s - lead


def read(ctx):
    try:
        from repro.obs import current
    except ImportError:
        return None
    obs = current()
    if obs is None or not ctx.devices:
        return None
    spans = [(n, t0 / 1e9, (t0 + d) / 1e9)
             for n, ph, t0, d, _ in obs.tracer.events() if ph == "X"]
    waits = _merge([t0, t1] for n, t0, t1 in spans if n == "transport.wait")
    off = ctx._clock_offset()
    if not waits or off is None:
        return None
    w0, w1 = ctx.window
    shares = []
    for dev in ctx.devices:
        gaps = [(g0 - off, g1 - off) for g0, g1 in dev["gaps"]]
        edge_s = ctx.window_s - dev["busy_s"] - sum(g1 - g0 for g0, g1 in gaps)
        lead, trail = _edges(ctx, spans, max(edge_s, 0.0))
        idle = [(w0, w0 + lead), *gaps, (w1 - trail, w1)]
        host = 0.0
        for a, b in idle:
            a, b = max(a, w0), min(b, w1)
            if b > a:
                host += (b - a) - _overlap(a, b, waits)
        shares.append(100.0 * host / ctx.window_s)
    return sum(shares) / len(shares)

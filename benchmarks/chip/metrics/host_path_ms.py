"""Host time of the front door and the stream's packing per table step, in
ms: the program's ``transport.decode`` and ``stream.pack[_pieces]`` spans
inside the traced window over the steps dispatched in it.  Harvest is left
out: it waits on the device."""


def read(ctx):
    steps = ctx.steps_dispatched()
    if not steps:
        return None
    spans = ctx.host_spans("transport.decode", "stream.pack",
                           "stream.pack_pieces")
    return 1e3 * sum(spans) / steps

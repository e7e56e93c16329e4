"""Device time of one table step (``_table_step`` / ``_table_step_pieces``
module runs in the trace), averaged over the steps the traced window holds,
in ms."""


def read(ctx):
    return ctx.step_ms()

"""Share of the Lloyd k-means work that a lane needed, in %.

A table step runs ``lloyd_iters`` Lloyd calls over every lane of the table
for each digitize trip and each k-growth round, until its widest span and
its slowest lane are done.  The program counts that work per step
(``repro.core.digitize.DigitizeWork``) and puts it on its
``stream.harvest[_pieces]`` span: ``lane_runs``, every lane of every run,
and ``useful_runs``, the lanes digitizing a piece or still growing k.  The
share is their sums over the steps harvested in the traced window, read
from the program's recorder (``repro.obs.current()``, which keeps the span
arguments that ``trace_reduce.Context.spans`` drops).  The rest is what
lane compaction or bucketing could leave out.  ``None`` where the program
records no such counts.
"""


def read(ctx):
    try:
        from repro.obs import current
    except ImportError:
        return None
    obs = current()
    if obs is None:
        return None
    w0, w1 = ctx.window
    lane_runs = useful_runs = 0
    for name, ph, t0, _, args in obs.tracer.events():
        if (ph == "X" and name.startswith("stream.harvest") and args
                and "lane_runs" in args and w0 <= t0 / 1e9 <= w1):
            lane_runs += args["lane_runs"]
            useful_runs += args["useful_runs"]
    return 100.0 * useful_runs / lane_runs if lane_runs else None

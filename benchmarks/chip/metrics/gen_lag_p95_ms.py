"""How late the load generator sent: the 95th percentile, over the frames
due inside the window, of the time a frame left the generator minus its
due time, in ms.  A starved generator would flatter the server."""

import numpy as np


def read(ctx):
    lag = ctx.loadgen["lag_s"]
    return 1e3 * float(np.percentile(lag, 95)) if lag else None

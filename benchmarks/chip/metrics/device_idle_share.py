"""Share of the traced window in which no op ran on the device:
1 - (union of the ``XLA Ops`` intervals / window), in %, averaged over the
chips."""


def read(ctx):
    if not ctx.devices:
        return None
    busy, window, _ = ctx.device_summary()
    return 100.0 * (1.0 - busy / window)

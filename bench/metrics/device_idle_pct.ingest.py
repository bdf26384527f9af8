"""Device idle share of the traced window in the ingest cell: 100 x (1 -
busy / window), busy being the union of the device's operation intervals."""


def read(ctx):
    t = ctx.trace
    if t is None or t.n_devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)

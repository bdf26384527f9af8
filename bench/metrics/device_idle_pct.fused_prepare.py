"""Device idle time the trace puts under the ``obs:fused_prepare``
annotation (plan rebuilds, degree-row pull, host prepare and merge of a
fused flush's pruned members), as a share of the traced window; left out
where the trace has no device plane or no such span."""
ANNOTATION = "obs:fused_prepare"


def read(ctx):
    t = ctx.trace
    if t is None or t.n_devices == 0 or t.window_s <= 0:
        return None
    if not any(a[0] == ANNOTATION for a in t.annotations):
        return None
    lo, hi = t.window_ns
    return 100.0 * t.idle_by_host.get(ANNOTATION, 0.0) / (hi - lo)

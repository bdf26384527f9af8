"""Engine ingest: time of the engine's ingest spans per edge applied."""

SPANS = ("ingest",)


def read(ctx):
    edges = sum(u.edges for u in ctx.updates if u.ok)
    ms = sum(s.duration_ms for s in ctx.spans if s.name in SPANS)
    return ms * 1e3 / edges if edges and ms > 0 else None

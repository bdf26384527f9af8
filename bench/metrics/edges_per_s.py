"""Edge updates applied (effective inserts plus expiries, as the service
reports them) over the whole window."""


def read(ctx):
    edges = sum(u.edges for u in ctx.updates if u.ok)
    span = ctx.window[1] - ctx.window[0]
    return edges / span if edges and span > 0 else None

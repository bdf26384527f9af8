"""Host-to-device bytes the program counted on the window's spans
(``h2d_bytes``), per edge applied."""
from bench import spans


def read(ctx):
    counted = [s.attrs["h2d_bytes"] for s in ctx.spans
               if "h2d_bytes" in s.attrs]
    edges = spans.edges_applied(ctx)
    if not counted or not edges:
        return None
    return sum(counted) / edges

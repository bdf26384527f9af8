"""Registry and fused bucket ingest: time of the window's ``service``
spans of ``op=ingest_many`` (host staging of every tenant's batch and one
``[T, B]`` scatter per bucket) over the edges they applied (their
``n_edges``: effective inserts plus deletes); left out where no such span
carries ``n_edges``."""
from bench import spans


def read(ctx):
    grouped = [s for s in spans.named(ctx, "service")
               if s.labels.get("op") == "ingest_many" and "n_edges" in s.attrs]
    edges = sum(s.attrs["n_edges"] for s in grouped)
    ms = sum(s.duration_ms for s in grouped)
    return ms * 1e3 / edges if edges and ms > 0 else None

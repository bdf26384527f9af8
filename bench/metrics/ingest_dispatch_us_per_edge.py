"""Device hand-off of ingest: the ``ingest_dispatch`` spans (scatter row
build, upload and enqueue) and the ``engine_resync`` spans under an
``ingest`` span (full re-uploads on a regrow), per edge applied."""
from bench import spans


def read(ctx):
    ingest = {s.span_id for s in spans.named(ctx, "ingest")}
    ms = [s.duration_ms
          for s in spans.named(ctx, "ingest_dispatch", "engine_resync")
          if s.parent_id in ingest]
    return spans.us_per_edge(ctx, sum(ms)) if ms else None

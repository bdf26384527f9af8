"""Host buffer bookkeeping of ingest: self time of the ``buffer_apply``
spans (endpoint check and ``EdgeBuffer.apply``), less the tombstone
compactions inside them, per edge applied."""
from bench import spans


def read(ctx):
    applies = {s.span_id: s for s in spans.named(ctx, "buffer_apply")}
    if not applies:
        return None
    ms = sum(s.duration_ms for s in applies.values())
    ms -= sum(s.duration_ms for s in spans.named(ctx, "buffer_compact")
              if s.parent_id in applies)
    return spans.us_per_edge(ctx, ms)

"""Mean duration of the window's ``buffer_compact`` spans (the O(E) slot
rebuild of ``EdgeBuffer.epoch_compact``, under an ingest or a refresh);
left out when none ran."""
from bench import spans


def read(ctx):
    ms = [s.duration_ms for s in spans.named(ctx, "buffer_compact")]
    return sum(ms) / len(ms) if ms else None

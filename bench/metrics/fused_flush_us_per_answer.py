"""Fused bucket query: time of the window's ``fused_flush`` spans (one
per bucket and coalesced flush: prepare, batched peels, read-backs, mask
write) over the answers they gave (their ``members``); left out where no
such span ran."""
from bench import spans


def read(ctx):
    flushes = [s for s in spans.named(ctx, "fused_flush")
               if "members" in s.attrs]
    answers = sum(s.attrs["members"] for s in flushes)
    ms = sum(s.duration_ms for s in flushes)
    return ms * 1e3 / answers if answers and ms > 0 else None

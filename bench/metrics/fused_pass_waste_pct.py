"""Fused batched peel: share of the batched lane-passes spent on lanes
that had converged or only pad the group, 100 x (1 - sum of the members'
own passes / sum of padded group lanes x passes), over the window's
``fused_flush`` spans (``member_passes``, ``lane_passes``); left out where
no flush carries them or none ran a batched peel."""
from bench import spans


def read(ctx):
    flushes = [s for s in spans.named(ctx, "fused_flush")
               if "lane_passes" in s.attrs and "member_passes" in s.attrs]
    lanes = sum(s.attrs["lane_passes"] for s in flushes)
    if not lanes:
        return None
    return 100.0 * (1.0 - sum(s.attrs["member_passes"] for s in flushes)
                    / lanes)

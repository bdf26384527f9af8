"""Fused bucket query: share of the flushed answers that were epoch
refreshes re-anchored inside their bucket's flush, 100 x sum of
``n_reanchored`` / sum of ``members`` over the window's ``fused_flush``
spans; left out where no flush counts its re-anchored members (a program
that refreshes its fused tenants one at a time, outside the flush)."""
from bench import spans


def read(ctx):
    flushes = [s for s in spans.named(ctx, "fused_flush")
               if "n_reanchored" in s.attrs and "members" in s.attrs]
    members = sum(s.attrs["members"] for s in flushes)
    if not members:
        return None
    return 100.0 * sum(s.attrs["n_reanchored"] for s in flushes) / members

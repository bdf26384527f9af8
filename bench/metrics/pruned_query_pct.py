"""Share of the window's answers (``query`` and ``refresh`` spans) that
peeled the pruned candidate buckets (``path`` is ``pruned``) rather than
the full width."""
from bench import spans


def read(ctx):
    paths = [s.attrs.get("path") for s in spans.named(ctx, "query", "refresh")]
    if not paths:
        return None
    return 100.0 * sum(p == "pruned" for p in paths) / len(paths)

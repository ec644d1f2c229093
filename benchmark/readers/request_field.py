"""A percentile of one field of the finished requests' serving records
(``result.resilience["serving"]`` as the engine wrote it), over the
requests due inside the window. Args: ``field``, ``q`` (the percentile),
``scale`` (1000 turns seconds into ms)."""

from benchmark.harness.stats import percentile


def read(ctx, *, field, q: float, scale: float = 1.0):
    vals = [r["serving"][field] for r in ctx.get("requests", ())
            if r.get("in_window") and r.get("serving")
            and r["serving"].get(field) is not None]
    if not vals:
        return None
    return percentile(vals, q) * scale

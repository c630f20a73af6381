"""Mean host time to build one window's JDCR instance from its drawn
requests (span ``build``), in ms."""
import statistics


def read(ctx, state):
    spans = ctx.spans.get("build")
    return 1e3 * statistics.fmean(spans) if spans else None

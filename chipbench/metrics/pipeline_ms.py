"""Mean host time of one ``cocar_grid`` call, LP to metrics, ended when
its results are on the host (span ``pipeline``), in ms."""
import statistics


def read(ctx, state):
    spans = ctx.spans.get("pipeline")
    return 1e3 * statistics.fmean(spans) if spans else None

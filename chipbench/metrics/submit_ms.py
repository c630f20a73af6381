"""Mean host time of one ``EdgeCluster.submit`` call (span ``submit``),
in ms: routing, and the pod's prefill and decode of its batch until the
last token is on the host."""
import statistics


def read(ctx, state):
    spans = ctx.spans.get("submit")
    return 1e3 * statistics.fmean(spans) if spans else None

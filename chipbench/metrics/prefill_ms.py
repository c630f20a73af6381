"""Mean time of one served batch's prefill, in ms: the program's span
``serve.prefill`` (``EdgePod.serve_batch``), from the prefill's dispatch
until its greedy token is on the host, over the window's batches."""
import statistics

from chipbench.spans import window_spans


def read(ctx, state):
    spans = window_spans(ctx)
    values = [sp.seconds for sp in spans or () if sp.name == "serve.prefill"]
    return 1e3 * statistics.fmean(values) if values else None

"""Time of one decode step, in ms: the program's spans ``serve.decode``
(``EdgePod.serve_batch``, from the first decode dispatch until the last
token is on the host) summed over the window, over the steps they ran
(their ``steps`` attribute)."""
from chipbench.spans import window_spans


def read(ctx, state):
    spans = [sp for sp in window_spans(ctx) or ()
             if sp.name == "serve.decode"]
    steps = sum(int(sp.attrs.get("steps", 0)) for sp in spans)
    if not steps:
        return None
    return 1e3 * sum(sp.seconds for sp in spans) / steps

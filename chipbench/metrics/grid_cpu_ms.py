"""The host CPU time of the offline pipeline per decision, in ms: the
thread CPU time (``time.thread_time``) of the program's span
``run_grid``, the whole ``cocar_grid`` call, over the window, per
decision completed.  Against ``pipeline_ms`` it says how much of a
window the host thread was running rather than waiting."""
from chipbench.spans import per_decision_ms


def read(ctx, state):
    return per_decision_ms(ctx, ("run_grid",), field="cpu_seconds")

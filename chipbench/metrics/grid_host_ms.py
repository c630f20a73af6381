"""The grid executor's host work per decision, in ms: the wall time of
the program's spans ``grid.prepare`` (bucket plan, rounding draws,
stacking), ``grid.put`` (inputs to the device), ``grid.dispatch`` (the
compiled call until it returns) and ``grid.unstack`` (outputs back to
per-window decisions), over the window, per decision completed."""
from chipbench.spans import per_decision_ms

SPANS = ("grid.prepare", "grid.put", "grid.dispatch", "grid.unstack")


def read(ctx, state):
    return per_decision_ms(ctx, SPANS)

"""The copy of finished outputs to the host per decision, in ms: the
wall time of the program's span ``grid.fetch`` (``np.asarray`` on
outputs that are ready), over the window, per decision completed."""
from chipbench.spans import per_decision_ms


def read(ctx, state):
    return per_decision_ms(ctx, ("grid.fetch",))

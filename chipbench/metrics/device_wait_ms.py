"""The host's wait for the device per decision, in ms: the wall time of
the program's span ``grid.wait`` (``jax.block_until_ready`` on a
dispatched call's outputs, the device's work the host does not
overlap), over the window, per decision completed."""
from chipbench.spans import per_decision_ms


def read(ctx, state):
    return per_decision_ms(ctx, ("grid.wait",))

"""Mean time a served request waited in the queue, in ms: from its
scheduled arrival to the start of the ``EdgeCluster.submit`` call that
served it."""
import numpy as np


def read(ctx, state):
    start = getattr(state, "start", None)
    if start is None or np.isnan(start).all():
        return None
    done = ~np.isnan(start)
    return 1e3 * float(np.mean(start[done] - state.arrivals.at[done]))

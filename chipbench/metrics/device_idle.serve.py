"""Share of the traced window in which no operation ran on the device,
in %: 100 * (1 - busy / window), busy being the union of the device's
operation intervals in the profiler's trace."""


def read(ctx, state):
    busy, window = ctx.info.get("busy_s"), ctx.info.get("window_s")
    if not busy or not window:
        return None
    return 100.0 * (1.0 - busy / window)

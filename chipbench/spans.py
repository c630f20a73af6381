"""Per-window readings of the program's own spans (``repro.obs.tracing``)
over the measured window.

The program records a span around each stage of a grid run; the tracer
keeps the newest of them, and ``Tracer.since`` reads those that started
after a point in time.  A program without ``since`` or without the
spans read gives no reading (``None``), and raises nothing; so does a
window that opened more spans than the tracer keeps.
"""


def window_spans(ctx):
    """The program's spans that started inside the measured window, in
    start order, or ``None`` where the program keeps none to read, or
    where the tracer's ring may have dropped some of them: the oldest
    span it keeps is of the window, and older ones were dropped."""
    from repro.obs import tracing

    tracer = tracing.TRACER
    since = getattr(tracer, "since", None)
    if since is None:
        return None
    spans = since(ctx.window_t0)
    if spans and spans[0].index > 0 and spans[0] is tracer.spans[0]:
        return None
    return [sp for sp in spans if sp.t0 < ctx.window_t1]


def per_decision_ms(ctx, names, field: str = "seconds"):
    """The sum of ``field`` (``seconds`` or ``cpu_seconds``) over the
    window's spans named in ``names``, per decision completed, in ms;
    ``None`` without decisions or without such spans."""
    n = ctx.counters.get("decisions", 0)
    spans = window_spans(ctx)
    if not n or spans is None:
        return None
    values = [getattr(sp, field) for sp in spans if sp.name in names]
    if not values:
        return None
    return 1e3 * sum(values) / n

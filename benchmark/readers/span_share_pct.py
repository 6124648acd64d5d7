"""Share of the window the host spent inside one of the benchmark's spans."""


def read(ctx, span):
    durations = ctx["spans"].durations(span)
    if not durations or ctx["window_s"] <= 0:
        return None
    return 100.0 * sum(durations) / ctx["window_s"]

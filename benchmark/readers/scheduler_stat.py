"""A number of the scheduler's ``stats()`` as it stands at the window's
close (cumulative since the scheduler started: warm-up and lead-in
included).  ``None`` where the program has no such key, or where
``nonzero_key`` is given and reads 0 (the program counted nothing)."""


def read(ctx, key, nonzero_key=None):
    end = ctx.get("stats_end")
    if not end or key not in end:
        return None
    if nonzero_key is not None and not end.get(nonzero_key):
        return None
    return float(end[key])

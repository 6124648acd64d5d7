"""One number of the scheduler's ``stats()`` over another, as they stand at
the window's close, as a percentage.  ``None`` where the program has
either key not, or the denominator reads 0 (nothing held, nothing
counted)."""


def read(ctx, key, over):
    end = ctx.get("stats_end")
    if not end or key not in end or not end.get(over):
        return None
    return 100.0 * float(end[key]) / float(end[over])

"""1 - (seconds an operation ran on the device) / (traced window), mean
over the chips traced."""


def read(ctx):
    profile = ctx.get("profile")
    if not profile:
        return None
    return 100.0 * (1.0 - profile["busy_s"] / profile["window_s"])

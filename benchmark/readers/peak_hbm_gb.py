"""Peak device memory on the fullest chip after the window (live buffers
plus the largest loaded program's scratch, see ``harness/device.py``), in
GB (1e9)."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return peak / 1e9 if peak else None

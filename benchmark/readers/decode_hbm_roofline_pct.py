"""The least time the chip's memory could take to feed one decode step
(``harness/decode_bytes.py``: the weights every token uses read once, the
held experts that got a token by the program's counter, the live rows'
cached latents by the scheduler's lengths, over the peak bandwidth) over
the step's median device time in the traced window, as a percentage.
``None`` where the program counts no expert assignments (a program without
such layers, or a parent commit without the counter)."""

from benchmark.harness import decode_bytes, modules, program
from benchmark.harness.stats import median


def _window_mean(start, end, key, per):
    """Mean of a per-``per`` average over the window alone, from the
    cumulative averages before and after it."""
    count = end[per] - start[per]
    if count <= 0:
        return None
    return (end[key] * end[per] - start[key] * start[per]) / count


def read(ctx, module="decode", per="megastep"):
    start, end = ctx.get("stats_start"), ctx.get("stats_end")
    if not start or not end or "moe_active_experts_per_step" not in end:
        return None
    events = modules.launches(ctx, module)
    active = _window_mean(start, end, "moe_active_experts_per_step",
                          "moe_layer_steps")
    live = _window_mean(start, end, "decode_live_positions", "iterations")
    if not events or active is None or live is None:
        return None
    steps = float(ctx["cell"].cell["scheduler"][per])
    step_s = median([e.seconds for e in events]) / steps
    cost = decode_bytes.decode_step_bytes(
        program.shape_of(ctx["cell"].config),
        active_experts_per_layer=active, live_positions=live)
    floor_s = cost["total"] / float(ctx["peaks"]["hbm_bytes_per_s"])
    ctx["say"]("decode_hbm_floor", step_ms=1e3 * step_s,
               floor_ms=1e3 * floor_s, active_experts_per_layer=active,
               live_positions=live, bytes=cost)
    return 100.0 * floor_s / step_s

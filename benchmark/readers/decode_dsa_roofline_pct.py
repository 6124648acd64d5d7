"""The least time the chip's memory could take to feed one decode step of a
latent-attention decoder with a learned indexer
(``harness/decode_bytes_dsa.py``: the weights every token uses read once,
the held experts that got a token by the program's counter, the latent rows
of the positions the live rows' attention selects on every layer and the
index keys of all they hold on the ``full`` layers, by the scheduler's
counts, over the peak bandwidth) over the step's median device time in the
traced window, as a percentage.  ``None`` where the program counts no
selected positions (a family without an indexer, or a parent commit
without the counter)."""

from benchmark.harness import decode_bytes_dsa, modules, program
from benchmark.harness.stats import median
from benchmark.readers.decode_hbm_roofline_pct import _window_mean


def read(ctx, module="decode", per="megastep"):
    start, end = ctx.get("stats_start"), ctx.get("stats_end")
    needed = ("moe_active_experts_per_step", "decode_live_positions",
              "decode_selected_positions")
    if not start or not end or any(
            key not in stats for key in needed for stats in (start, end)):
        return None
    events = modules.launches(ctx, module)
    active = _window_mean(start, end, "moe_active_experts_per_step",
                          "moe_layer_steps")
    live = _window_mean(start, end, "decode_live_positions", "iterations")
    selected = _window_mean(start, end, "decode_selected_positions",
                            "iterations")
    if not events or None in (active, live, selected):
        return None
    steps = float(ctx["cell"].cell["scheduler"][per])
    step_s = median([e.seconds for e in events]) / steps
    cost = decode_bytes_dsa.decode_step_bytes(
        program.shape_of(ctx["cell"].config),
        active_experts_per_layer=active, live_positions=live,
        selected_positions=selected)
    floor_s = cost["total"] / float(ctx["peaks"]["hbm_bytes_per_s"])
    ctx["say"]("decode_dsa_floor", step_ms=1e3 * step_s,
               floor_ms=1e3 * floor_s, active_experts_per_layer=active,
               live_positions=live, selected_positions=selected, bytes=cost)
    return 100.0 * floor_s / step_s

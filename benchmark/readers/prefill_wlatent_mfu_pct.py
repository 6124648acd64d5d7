"""The model's operations in the prefill chunks of the traced window
(``harness/prefill_flops_wlatent.py``, from each chunk's place in its prompt
as the program's own ``prefill_chunk`` spans give it and the share of the
router's choices that fall on held experts as the device counted it) over
the chip's peak bf16 FLOP/s and over those launches' device time, as a
percentage.  The launches are the trace's (``module``); the operations are
the spans' mean a chunk times the launches (``readers/prefill_mfu_pct.py``
says why).  ``None`` where the program records no such span, counts no
assignments, or has not both a window ring and a selection."""

from benchmark.harness import (
    modules, prefill_flops_wlatent, program, program_spans)


def read(ctx, module="prefill", span="dtt/serve/prefill_chunk"):
    end = ctx.get("stats_end")
    found = program_spans.collect(ctx)
    chunks = found["ended"].get(span) if found else None
    events = modules.launches(ctx, module)
    if (not end or not chunks or not events
            or "decode_live_positions_window" not in end
            or "decode_selected_positions" not in end):
        return None
    here = float(end.get("moe_assignments_here", 0.0))
    routed = here + float(end.get("moe_assignments_absent", 0.0))
    if routed <= 0 or any(
            "offset" not in args or "chunk_tokens" not in args
            for _, args in chunks):
        return None
    shape = program.shape_of(ctx["cell"].config)
    flops = [prefill_flops_wlatent.prefill_chunk_flops(
        shape, offset=int(args["offset"]), tokens=int(args["chunk_tokens"]),
        assignments_here_share=here / routed) for _, args in chunks]
    device_s = sum(e.seconds for e in events)
    mean = lambda part: sum(f[part] for f in flops) / len(flops)
    total = len(events) * mean("total")
    ctx["say"]("prefill_wlatent_mfu", launches=len(events),
               chunk_spans=len(chunks), device_s=device_s, flops=total,
               assignments_here_share=here / routed,
               flops_a_chunk={part: mean(part) for part in flops[0]},
               mean_context=sum(int(a["offset"]) + int(a["chunk_tokens"])
                                for _, a in chunks) / len(chunks))
    return 100.0 * total / float(ctx["peaks"]["bf16_flops_per_s"]) / device_s

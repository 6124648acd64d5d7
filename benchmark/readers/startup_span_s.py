"""Seconds of set-up that the program's own ``dtt/startup/<phase>`` spans of
the given names cover (their union: a phase met twice is counted once where
the two overlap), over the spans that end before the window opens.

The program records two categories whether or not anybody traces
(``obs/trace.py``): ``startup``, the phases it goes through between process
start and its loop, and ``compile``, each trace, lowering and backend
compile (or cache read) JAX reports, by program (``compile_cache.py``).
``collect`` takes both from the ring once a run, keeps what ended before
the window opened (the reference's and the comparison's compiles come
after its close and fall out), tells the program's compiles from the
harness's own jitted helpers', and says one ``startup`` line.  A program
that records neither (a commit before it did) gives ``None``, and the three
readers built on this leave their metrics out of the line."""

from benchmark.harness import xplane

_KEY = "startup_spans"


def collect(ctx):
    """``phases`` (the ``dtt/startup/*`` spans), ``compiles`` (the
    ``dtt/compile/*`` spans with a ``parent``: recorded while a span of
    the program was open on the compiling thread, so the program's own)
    and ``helpers`` (those with none: ``weights.make_params``,
    ``leaf_norms``, the yardstick's bill), each as ``Tracer.spans`` gives
    them and all ended before the window opened."""
    if _KEY in ctx:
        return ctx[_KEY]
    ctx[_KEY] = None
    anchor = ctx["spans"].by_name.get("window") if ctx.get("spans") else None
    if not anchor:
        return None
    from distributed_tensorflow_tpu.obs.trace import default_tracer

    opened = anchor[-1][0]
    tracer = default_tracer()
    before = lambda cat: [s for s in tracer.spans(cat=cat) if s[2] <= opened]
    phases, compiles = before("startup"), before("compile")
    if not phases and not compiles:
        return None
    found = {
        "phases": phases,
        "compiles": [s for s in compiles if "parent" in s[4]],
        "helpers": [s for s in compiles if "parent" not in s[4]],
    }
    ctx[_KEY] = found
    if "say" in ctx:
        from distributed_tensorflow_tpu.obs import startup

        said = startup.summarize(phases, found["compiles"])
        first = min(s[1] for s in phases + compiles)
        ctx["say"]("startup", first_span_to_window_s=opened - first,
                   **said, harness_helpers=startup.summarize(
                       [], found["helpers"])["programs"])
    return found


def seconds(spans):
    """Seconds covered by at least one of the spans."""
    return xplane.total(xplane.union((s[1], s[2]) for s in spans))


def read(ctx, spans):
    found = collect(ctx)
    if not found:
        return None
    mine = [s for s in found["phases"] if s[0] in spans]
    return seconds(mine) if mine else None

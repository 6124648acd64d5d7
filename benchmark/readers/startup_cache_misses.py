"""How many of the program's backend compiles before the window opened were
not read from the persistent compile cache: its ``dtt/compile/backend``
spans (``startup_span_s.collect``) whose ``cache`` says ``miss``.  0 in a
warm run; more says that a program which should have been read was compiled
again (a cache key that moved), and the ``startup`` line names it."""

from benchmark.readers.startup_span_s import collect


def read(ctx):
    found = collect(ctx)
    if not found:
        return None
    backends = [s for s in found["compiles"] if s[0] == "dtt/compile/backend"]
    if not backends:
        return None
    return float(sum(s[4].get("cache") == "miss" for s in backends))

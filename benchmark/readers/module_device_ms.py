"""Median device time of one launch of a jitted program, from the trace's
``XLA Modules`` line, over ``per`` (inner steps fused per launch; a string
names the scheduler argument that holds it).  ``module`` says which
program, by the rule in ``harness/modules.py``."""

from benchmark.harness import modules
from benchmark.harness.stats import median


def read(ctx, module="heaviest", per=1):
    events = modules.launches(ctx, module)
    if not events:
        return None
    if isinstance(per, str):
        per = ctx["cell"].cell["scheduler"][per]
    return 1e3 * median([e.seconds for e in events]) / float(per)

"""``pytest benchmark/tests`` from the repository root, on the CPU.  These
tests are the benchmark's own (outside tier-1); ``tests/`` is not touched."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

FIXTURES = os.path.join(REPO, "benchmark", "tests", "fixtures")

"""What the suite may not do to its own clock (ROADMAP D8)."""

import ast
import glob
import os

LONGEST_LITERAL_SLEEP_S = 20


def test_no_test_sleeps_for_a_literal_20_seconds_or_more():
    """A test that waits for something polls for it, with a limit: a
    ``time.sleep(60)`` "to give it time" holds a worker for a minute on a
    fast machine and is too short on a slow one."""
    found = []
    for path in sorted(glob.glob(
            os.path.join(os.path.dirname(__file__), "test_*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call) and node.args
                    and ast.unparse(node.func) in ("time.sleep", "sleep")
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, (int, float))
                    and node.args[0].value >= LONGEST_LITERAL_SLEEP_S):
                found.append(f"{os.path.basename(path)}:{node.lineno} "
                             f"sleeps {node.args[0].value} s")
    assert not found, (
        f"a wait is a poll with a limit, not a sleep: {found}")

#!/usr/bin/env bash
# Tier-1 verify: the command the driver runs after every PR (`commands` in
# /root/TESTS_LAST_RUN.json, to the letter), so CI and humans run the same
# gate.  Prints DOTS_PASSED=<n> (passing tests: from the junit file, else
# from the progress lines) and exits with pytest's status.
#
# Usage: bash scripts/t1.sh   (from the repo root)
#
# '-m not slow' keeps the subprocess smokes (test_serve_smoke.py —
# cold-jit entrypoint runs) out of the gate; run them explicitly with:
#   python -m pytest tests/ -q -m slow
# Six xdist workers, one test file to a worker (--dist loadfile): the
# serve_slow suites are IN the gate and a serial run of them cannot end
# inside any limit.  ALLOW_MULTIPLE_LIBTPU_LOAD=1, as in the driver's own
# environment: three files load the TPU's compile-only library
# (tests/test_chip_compile.py, tests/test_chip_compile_train.py and
# tests/test_chip_compile_gpt2_serve.py), each in the worker it goes to,
# side by side.
#
# The static-analysis gate (scripts/lint.sh — dttlint + ruff when
# present) rides tier-1: a lint finding fails the gate even when every
# test passes, but never masks a test failure's exit code.
#
# DTT_SERVE_LOADGEN=1 adds an opt-in open-loop load-harness smoke AFTER
# the gate: a short seeded Poisson trace replays through serve.py with
# the lifecycle recorder attached (--loadgen_trace + --lifecycle_log),
# proving the goodput/breakdown JSON keys end to end.  Opt-in because it
# pays a cold-jit entrypoint run.
#
# DTT_SERVE_ASYNC=1 adds an opt-in deep-async pass AFTER the gate: the
# async suites rerun with the launch ring at depth 4 (DTT_ASYNC_DEPTH=4
# — three launches in flight behind every fetch), so the
# parity/composition claims are re-proven beyond the default double
# buffer.
cd "$(dirname "$0")/.." || exit 1
set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null)
bash scripts/lint.sh; lint_rc=$?
[ "$rc" -eq 0 ] && rc=$lint_rc
if [ "${DTT_SERVE_LOADGEN:-0}" = "1" ]; then
  timeout -k 10 900 env JAX_PLATFORMS=cpu \
    python serve.py --model=gpt2 --continuous \
    --loadgen_trace=poisson:n=12,rate=50 \
    --lifecycle_log=/tmp/_t1_lifecycle.jsonl \
    | python -c 'import json,sys; r=json.load(sys.stdin); \
assert "goodput_under_slo" in r and "shed_rate" in r \
and "breakdown_sum_to_wall_ratio" in r, sorted(r); \
print("LOADGEN_GOODPUT=%.3f" % r["goodput_under_slo"])'; loadgen_rc=$?
  [ "$rc" -eq 0 ] && rc=$loadgen_rc
fi
if [ "${DTT_SERVE_ASYNC:-0}" = "1" ]; then
  timeout -k 10 1800 env JAX_PLATFORMS=cpu DTT_ASYNC_DEPTH=4 \
    python -m pytest tests/test_serve_async.py -q -m serve_slow \
    -p no:cacheprovider -p no:xdist -p no:randomly; async_rc=$?
  [ "$rc" -eq 0 ] && rc=$async_rc
fi
exit $rc

#!/usr/bin/env bash
# Tier-1 verify wrapper: runs the tier-1 tests the way the driver runs
# them (`commands` of /root/TESTS_LAST_RUN.json: six xdist workers, one
# file per worker, 1,470 s) so docs, CI and humans stop copy-pasting it,
# then optionally the perf-regression gate. ROADMAP.md's "Tier-1
# verify" line is the single-process form of the same selection; it
# does not finish the suite inside its 870 s.
#
# Usage:
#   scripts/tier1.sh           # tier-1 tests only (exit = pytest rc)
#   scripts/tier1.sh --gate    # tests, then benchmarks/ci_gate.py
#                              # against benchmarks/baselines/seed.json
#
# The gate is opt-in because it runs the micro-benchmark suite (a few
# minutes of CPU) and its wall-clock metrics want an otherwise idle
# machine; the tests alone are the mandatory bar.

set -u
cd "$(dirname "$0")/.."

GATE=0
for a in "$@"; do
  [ "$a" = "--gate" ] && GATE=1
done

# The driver's command, but for ALLOW_MULTIPLE_LIBTPU_LOAD=1, which is
# the driver's to set: `--dist loadfile` keeps tests/test_chip_lowering.py
# (the one file that loads libtpu) in one worker. It ends in `exit $rc`,
# so it runs in a subshell and its exit status is captured here.
bash -c "set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=\${PIPESTATUS[0]}; echo DOTS_PASSED=\$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?\$' /tmp/_t1.log | tr -cd . | wc -c); exit \$rc"
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "tier1.sh: tier-1 tests FAILED (rc=$rc)" >&2
  exit "$rc"
fi

if [ "$GATE" = "1" ]; then
  echo "tier1.sh: running perf-regression gate" >&2
  python benchmarks/ci_gate.py --baseline benchmarks/baselines/seed.json
  rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "tier1.sh: perf gate FAILED (rc=$rc)" >&2
    exit "$rc"
  fi
fi
exit 0

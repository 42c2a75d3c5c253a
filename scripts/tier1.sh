#!/usr/bin/env bash
# Tier-1 as the driver runs it (ROADMAP.md "Tier-1 verify", docs/testing.md):
# lint, the chaos smoke, then ONE pytest invocation over tests/ with six
# xdist workers (one test file per worker at a time) under one timeout. The
# driver's own run was cut at 1,472 s of its 1,470 on PR 39's tree; on one
# sandbox that tree took 1,326 / 1,272 s (9,806 / 9,322 CPU s) and PR 40's
# 859 / 867 (6,070 / 6,165), four runs in turn (docs/testing.md "Tier-1").
# Prints DOTS_PASSED=<passed tests>; the worst exit code of the three wins,
# and a failing stage never stops the later ones.
#
# TIER1_LOG_DIR  where the three logs land (default /tmp)
set -u -o pipefail

cd "$(dirname "$0")/.."

LOG_DIR="${TIER1_LOG_DIR:-/tmp}"
mkdir -p "$LOG_DIR"
rc=0

# graftlint (docs/static-analysis.md): AST only, no JAX backend, seconds
timeout -k 5 120 python scripts/lint.py --json "$LOG_DIR/_t1_lint.json" \
  2>&1 | tee "$LOG_DIR/_t1_lint.log"
lint_rc=${PIPESTATUS[0]}
echo "LINT rc=${lint_rc}"
[ "$lint_rc" -ne 0 ] && rc=$lint_rc

# the fixed-seed self-healing fleet drill (scripts/chaos_smoke.py): about
# 45 s on the CPU; the timeout is headroom
timeout -k 5 180 env JAX_PLATFORMS=cpu python scripts/chaos_smoke.py \
  2>&1 | tee "$LOG_DIR/_t1_chaos.log"
chaos_rc=${PIPESTATUS[0]}
echo "CHAOS_SMOKE rc=${chaos_rc}"
[ "$chaos_rc" -ne 0 ] && [ "$rc" -eq 0 ] && rc=$chaos_rc

log="$LOG_DIR/_t1.log"
rm -f "$log"
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
  -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee "$log"
test_rc=${PIPESTATUS[0]}
[ "$test_rc" -ne 0 ] && [ "$rc" -eq 0 ] && rc=$test_rc
# pytest's -q progress lines are runs of [.FEsx] with an optional percentage
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$log" | tr -cd . | wc -c)"
exit "$rc"

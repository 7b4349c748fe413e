#!/usr/bin/env bash
# Tier-1 verify gate: the command the driver runs after every PR (the
# `commands` of its TESTS_LAST_RUN.json), wrapped so builders run one script.
#
#   scripts/tier1.sh                       # the whole gate (CPU, not-slow)
#   scripts/tier1.sh -k "plan or offload"  # extra pytest args pass through
#
# Six xdist workers, one test file to a worker (--dist loadfile), under the
# driver's 1,470 s limit; the log goes to $TMPDIR/_t1.log, the junit report
# to $TMPDIR/_t1.xml.  Prints DOTS_PASSED=<count> (the driver's count: the
# junit report's tests less errors, failures and skips) and WORKERS_DOWN=<n>,
# then exits with pytest's status.  The driver also sets
# ALLOW_MULTIPLE_LIBTPU_LOAD=1 for its own run; this script does not
# (tests/test_chip_compile.py describes the TPU inside one file's fixtures).
set -o pipefail
cd "$(dirname "$0")/.."
tmp="${TMPDIR:-/tmp}"
rm -rf "$tmp/_t1.log" "$tmp/_t1.xml"
timeout -k 10 1470 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly \
    --junitxml="$tmp/_t1.xml" "$@" 2>&1 | tee "$tmp/_t1.log"
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' "$tmp/_t1.xml" 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$tmp/_t1.log" | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' "$tmp/_t1.log" 2>/dev/null)
exit $rc

#!/bin/bash
# Tier-1 verify in one line — the pipeline the driver runs after every PR
# (six xdist workers, one test file per worker at a time), so builder and
# reviewer stop pasting it by hand. Prints the pass count (from the junit
# summary, falling back to the dots in pytest's progress lines) and exits
# with pytest's status.
#
#   scripts/t1.sh          # or: make t1
#
# Log lands in /tmp/_t1.log for post-mortems.
set -o pipefail
cd "$(dirname "$0")/.."
rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu \
  python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
  -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml \
  -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}
exit $rc

#!/bin/bash
# One call to the chip, as PR 22's measurements were made (PERF.md lists the
# arguments of each call):
#
#   chiprun --timeout <s> -- bash benchmark/tools/chip_call.sh <tag> <budget_s> <cell>:<trace>:<seed> ...
#
# Runs the cells one after the other from .archive_check/ where that copy of
# the committed tree exists (git archive $(git write-tree) | tar -x -C
# .archive_check), else in place, and keeps each run's output under
# chiprun_out/<tag>/. A run is not started once <budget_s> seconds of the call
# are gone. If the machine comes with a compile cache of its own, the
# benchmark's fixed cache directory is made a link to it, so that a second
# call finds the first one's programs; the benchmark itself never looks there.
set -u
tag=$1; budget=$2; shift 2
top=$PWD
out=$top/chiprun_out/$tag
mkdir -p "$out"
[ -d .archive_check ] && cd .archive_check
echo "running in $PWD; $(nproc) cores; JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
if [ -n "${JAX_COMPILATION_CACHE_DIR:-}" ] && [ -d "$JAX_COMPILATION_CACHE_DIR" ]; then
  mkdir -p benchmark/.cache
  [ -e benchmark/.cache/jax ] || ln -s "$JAX_COMPILATION_CACHE_DIR" benchmark/.cache/jax
  du -sh "$JAX_COMPILATION_CACHE_DIR/" | sed 's/^/machine cache at start: /'
fi
n=0
for spec in "$@"; do
  n=$((n + 1))
  IFS=: read -r cell trace seed <<<"$spec"
  if [ $SECONDS -gt "$budget" ]; then echo "run $n $spec: not started, $SECONDS s gone"; continue; fi
  t=$SECONDS
  python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds 51 --trace "$trace" \
    >"$out/run$n.out" 2>"$out/run$n.err"
  rc=$?
  echo "run $n $spec rc=$rc wall=$((SECONDS - t))s"
  cp benchmark/.cache/server.log "$out/server$n.log" 2>/dev/null
  cp benchmark/.cache/reference.log "$out/reference$n.log" 2>/dev/null
  grep -v '^\[window\] per request' "$out/run$n.out" | cut -c1-1500
  tail -c 1500 "$out/run$n.err"
done
du -sh benchmark/.cache/jax/ 2>/dev/null | sed 's/^/compile cache at end: /'
echo "call took $SECONDS s"

#!/bin/bash
# One call to the chip for PR 23's measurements (PERF.md §6 lists each call's arguments):
#
#   chiprun --timeout <s> -- bash tools/chip_runs.sh <tag> <budget_s> <dir>:<mode>:<cell>:<seed> ...
#
# Runs the specs one after the other, each from <dir> (a copy of a tree inside the repo, in a
# directory .gitignore lists: `git archive <tree> | tar -x -C <dir>`; `.` is the tree as it
# stands), and keeps each run's output under chiprun_out/<tag>/. <mode> is 0, 1 or 2 (run.py
# --trace <mode>), `gaps` (a --trace 2 run through benchmark/tools/gaps_by_span.py, which also
# prints the capture's tables), or 0c / 2c: the same under a HOME, XDG_CACHE_HOME and TMPDIR
# of their own that start empty, as the driver's check runs them. A run is not started once
# <budget_s> seconds of the call are gone.
set -u
tag=$1; budget=$2; shift 2
top=$PWD
out=$top/chiprun_out/$tag
mkdir -p "$out"
echo "$(nproc) cores; JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
n=0
for spec in "$@"; do
  n=$((n + 1))
  IFS=: read -r dir mode cell seed <<<"$spec"
  if [ $SECONDS -gt "$budget" ]; then echo "run $n $spec: not started, $SECONDS s gone"; continue; fi
  cd "$top/$dir" || { echo "run $n $spec: no directory $dir"; continue; }
  t=$SECONDS
  env=()
  case $mode in
    *c) home=$top/$dir/.clean_home; [ -d "$home" ] || mkdir -p "$home/home" "$home/cache" "$home/tmp"
        env=(HOME="$home/home" XDG_CACHE_HOME="$home/cache" TMPDIR="$home/tmp"); mode=${mode%c};;
  esac
  if [ "$mode" = gaps ]; then
    cmd=(python3 benchmark/tools/gaps_by_span.py --workload "$cell" --seed "$seed" --seconds 51)
  else
    cmd=(python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds 51 --trace "$mode")
  fi
  env "${env[@]}" "${cmd[@]}" >"$out/run$n.out" 2>"$out/run$n.err"
  rc=$?
  echo "run $n $spec rc=$rc wall=$((SECONDS - t))s"
  cp benchmark/.cache/server.log "$out/server$n.log" 2>/dev/null
  grep -v '^\[window\] per request' "$out/run$n.out" | cut -c1-2500
  tail -c 1500 "$out/run$n.err"
done
echo "call took $SECONDS s"

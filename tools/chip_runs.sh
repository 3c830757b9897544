#!/bin/bash
# One call to the chip for PR 23's measurements (PERF.md §6 lists each call's arguments):
#
#   chiprun --timeout <s> -- bash tools/chip_runs.sh <tag> <budget_s> <dir>:<mode>:<cell>:<seed>[:<seconds>] ...
#
# Runs the specs one after the other, each from <dir> (a copy of a tree inside the repo, in a
# directory .gitignore lists: `git archive <tree> | tar -x -C <dir>`; `.` is the tree as it
# stands), and keeps each run's output under chiprun_out/<tag>/. <mode> is 0, 1 or 2 (run.py
# --trace <mode>), `gaps` (a --trace 2 run through benchmark/tools/gaps_by_span.py, which also
# prints the capture's tables), `ledger` (the same through benchmark/tools/ledger_vs_trace.py: the
# completion ledger against the capture, and the window's sum), `witness` (tools/glm_deep_witness.py:
# the latent cache's deep context against the reference by logits, $WITNESS_ARGS after the cell and
# the seed), or 0c / 2c: the same under a HOME, XDG_CACHE_HOME and TMPDIR
# of their own that start empty, as the driver's check runs them. <seconds> is the measured window
# (51 where left out; a run made for its verdict alone takes 5). A run is not started once
# <budget_s> seconds of the call are gone, nor, with NEED="metric,metric" in the environment,
# after a run whose last line is not `correct` or lacks one of those metrics (the chip's
# minutes are better kept for the repaired tree). Each run's exit code, wall seconds and the processes
# it left running (none, or the driver refuses the benchmark) are also kept in <tag>/summary.txt.
set -u
tag=$1; budget=$2; shift 2
top=$PWD
out=$top/chiprun_out/$tag
mkdir -p "$out"
echo "$(nproc) cores; JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
n=0
for spec in "$@"; do
  n=$((n + 1))
  IFS=: read -r dir mode cell seed secs <<<"$spec"
  secs=${secs:-51}
  if [ $SECONDS -gt "$budget" ]; then echo "run $n $spec: not started, $SECONDS s gone"; continue; fi
  if [ -n "${fault:-}" ]; then echo "run $n $spec: not started, $fault"; continue; fi
  cd "$top/$dir" || { echo "run $n $spec: no directory $dir"; continue; }
  t=$SECONDS
  env=()
  case $mode in
    *c) home=$top/$dir/.clean_home; [ -d "$home" ] || mkdir -p "$home/home" "$home/cache" "$home/tmp"
        env=(HOME="$home/home" XDG_CACHE_HOME="$home/cache" TMPDIR="$home/tmp"); mode=${mode%c};;
  esac
  if [ "$mode" = gaps ]; then
    cmd=(python3 benchmark/tools/gaps_by_span.py --workload "$cell" --seed "$seed" --seconds 51)
  elif [ "$mode" = ledger ]; then
    cmd=(python3 benchmark/tools/ledger_vs_trace.py --workload "$cell" --seed "$seed" --seconds 51)
  elif [ "$mode" = witness ]; then
    # shellcheck disable=SC2086
    cmd=(python3 tools/glm_deep_witness.py --workload "$cell" --seed "$seed" ${WITNESS_ARGS:-})
  else
    cmd=(python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$secs" --trace "$mode")
  fi
  env "${env[@]}" "${cmd[@]}" >"$out/run$n.out" 2>"$out/run$n.err"
  rc=$?
  # what the run left behind: the driver refuses a benchmark whose run leaves a process running
  left=$(ps -eo pid,ppid,etimes,args | grep -E 'benchmark[./]|server_child|probe_child|distributed_llama' | grep -v grep)
  echo "run $n $spec rc=$rc wall=$((SECONDS - t))s left_running=$(printf '%s' "$left" | grep -c .)" | tee -a "$out/summary.txt"
  [ -n "$left" ] && printf '%s\n' "$left" | cut -c1-300 | tee -a "$out/summary.txt"
  cp benchmark/.cache/server.log "$out/server$n.log" 2>/dev/null
  for f in benchmark/.cache/reference*.json; do [ -f "$f" ] && cp "$f" "$out/run$n.$(basename "$f")"; done
  grep -v '^\[window\] per request' "$out/run$n.out" | cut -c1-2500
  tail -c 1500 "$out/run$n.err"
  if [ -n "${NEED:-}" ]; then
    fault=$(tail -n 1 "$out/run$n.out" | NEED=$NEED python3 -c '
import json, os, sys
try:
    r = json.loads(sys.stdin.read())
except ValueError:
    sys.exit(print("the last line is no result"))
lacks = [m for m in os.environ["NEED"].split(",") if m not in r.get("metrics", {})]
if r.get("correct") is not True or r.get("failed") or lacks:
    print("correct %s, failed %s, lacks %s" % (r.get("correct"), r.get("failed"), lacks))')
    [ -n "$fault" ] && fault="run $n: $fault"
  fi
done
echo "call took $SECONDS s"

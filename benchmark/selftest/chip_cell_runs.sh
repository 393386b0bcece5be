#!/bin/bash
# Runs of one cell from one or more checkouts, in the order given, in one
# call to the chip, so that both sides of a pair see the same machine:
#
#   chiprun --timeout 2400 -- bash benchmark/selftest/chip_cell_runs.sh \
#       <workload> <trace 0|1> <dir>:<seed> [<dir>:<seed> ...]
#
# <dir> is a checkout under the repo's root: `.` for the tree as it stands,
# a `git archive` of the parent with this tree's BENCHMARK.json and
# benchmark/ laid over it (what the driver compares with), or an archive of
# `git write-tree` (the committed files alone). Six seeds of a cell:
# `.:s1 .:s2 ...`; a pair: `parent:s1 .:s1 .:s2 parent:s2`. Each run's
# [serve] / [train] / [stall] lines and its result line go to stdout and to
# chiprun_out/cell_runs.log (or the file CELL_RUNS_LOG names there, for
# several cells in one call), with the directory and the seed in front.
root=$PWD; mkdir -p chiprun_out
out=$root/chiprun_out/${CELL_RUNS_LOG:-cell_runs.log}; : > "$out"
workload=$1; trace=$2; shift 2
for run in "$@"; do
  dir=${run%%:*}; seed=${run##*:}
  began=$SECONDS
  ( cd "$dir" && python3 benchmark/run.py --workload "$workload" --seed "$seed" \
      --seconds 51 --trace "$trace" 2>&1 \
      | grep -E '^\[(serve|train|trace|stall|gaps|gaps-hist|loop)\]|^\{"correct"|^compared |Error' \
      | sed "s|^|$dir $seed |" | tee -a "$out"
    # a serve cell's parity legs are printed where the reference runs: the
    # replica's log
    grep -h "\[parity\]" "${TMPDIR:-/tmp}"/ray_tpu/session_*/logs/*.log \
      2>/dev/null | tail -1 | sed "s|^|$dir $seed |" | tee -a "$out" )
  # the whole run, start of the process to its exit, against the 360 s a
  # run may take (1,200 s where it compiles)
  echo "$dir $seed [took] $((SECONDS - began)) s" | tee -a "$out"
done

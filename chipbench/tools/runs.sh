#!/bin/sh
# Run benchmark commands one after another and keep their output:
#   sh chipbench/tools/runs.sh <tag> "<run.py or control.py args>" ...
# Each command's standard output and error go to chiprun_out/runs/<tag>.<n>.*
tag=$1; shift
mkdir -p chiprun_out/runs
n=0
for a in "$@"; do
  n=$((n + 1))
  case "$a" in
    control*) prog=chipbench/control.py; a=${a#control } ;;
    *) prog=chipbench/run.py ;;
  esac
  start=$(date +%s)
  python3 $prog $a > chiprun_out/runs/$tag.$n.out 2> chiprun_out/runs/$tag.$n.err
  rc=$?
  echo "== $n rc=$rc wall=$(( $(date +%s) - start ))s $prog $a"
  tail -n 1 chiprun_out/runs/$tag.$n.out
  tail -n 4 chiprun_out/runs/$tag.$n.err
done

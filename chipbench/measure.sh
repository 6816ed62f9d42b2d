#!/bin/bash
# Chip measurements for the benchmark's definition, in one call: per cell a
# first (compiling) run, the calibration readings, two sets of 6 runs on the
# same seeds, and three traced runs.  A serving cell first sweeps its offered
# rate and prints 0.8 of the knee, to be committed to its traffic file.  Usage: [OUT=<dir>] measure.sh <seconds> <cells...>
cd "$(dirname "$0")/.."
O=${OUT:-chipbench_out/measure}; mkdir -p $O
S=$1; shift
note() { echo "$(date +%T) $*"; }
for cell in "$@"; do
  case $cell in
    *.serve)
      python3 chipbench/sweep.py --workload $cell --rates ${RATES:-500,1000,1500,2000,2500,3000,4000} \
        --seconds 4 > $O/sweep.$cell.out 2> $O/sweep.$cell.err
      note "sweep $cell rc=$?"; cat $O/sweep.$cell.out | cut -c1-400; tail -2 $O/sweep.$cell.err | cut -c1-300
      python3 chipbench/knee.py $O/sweep.$cell.out
      extra="--seconds $S";;
    *) extra="--faults half_batch";;
  esac
  python3 chipbench/run.py --workload $cell --seed 3100000000 --seconds $S --trace 0 > $O/warm.$cell.out 2> $O/warm.$cell.err
  note "warm $cell rc=$?"; tail -c 900 $O/warm.$cell.out; grep "^\[" $O/warm.$cell.err | tail -3 | cut -c1-300
  python3 chipbench/calibrate.py --workload $cell --seeds 3000000001-3000000012 \
     --control-seeds 3000000001-3000000003 $extra > $O/cal.$cell.out 2> $O/cal.$cell.err
  note "cal $cell rc=$?"; tail -2 $O/cal.$cell.err | cut -c1-300
  python3 chipbench/run.py --workload $cell --seed 3200000001 --seconds $S --trace 1 > $O/T.$cell.out 2> $O/T.$cell.err
  rc=$?; note "T $cell 1 rc=$rc"; tail -c 1500 $O/T.$cell.out; grep -v "^WARNING\|^I0000\|^W0000" $O/T.$cell.err | tail -3 | cut -c1-300
  python3 -c "import sys, glob; sys.path[:0] = ['.', 'src']; from chipbench import trace; \
f = sorted(glob.glob('chipbench_out/trace-$cell-*/**/*.xplane.pb', recursive=True)); print(trace.summary(f[-1], top=60))" \
    > $O/trace_summary.$cell.txt 2>&1
  for set in A B; do
    for i in 1 2 3 4 5 6; do
      python3 chipbench/run.py --workload $cell --seed 310000000$i --seconds $S --trace 0 >> $O/$set.$cell.out 2>> $O/$set.$cell.err
      note "$set $cell $i rc=$?"
    done
  done
  for i in 2 3; do
    python3 chipbench/run.py --workload $cell --seed 320000000$i --seconds $S --trace 1 >> $O/T.$cell.out 2>> $O/T.$cell.err
    note "T $cell $i rc=$?"
  done
  python3 chipbench/summarize.py $O $cell
done
du -sh .jax_cache chipbench_out

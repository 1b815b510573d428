#!/bin/bash
# Several runs of one cell in one chip call, each a new process, logs under
# $OUT/<tag>/ (default chiprun_out/, which the chip tool brings back):
#   bash benchmarks/tests/chip_runs.sh <cell> <seconds> <tag> <seed>:<trace> [...]
# Run it from the root of a checkout; a run that outlives 400 s is cut, and
# after each run the processes it left behind are listed (there should be none).
cell=$1; seconds=$2; tag=$3; shift 3
out=${OUT:-chiprun_out}/$tag
mkdir -p $out
for spec in "$@"; do
  seed=${spec%%:*}; trace=${spec##*:}
  base=$out/${cell}_${seed}_${trace}
  t_run=$(date +%s)
  timeout -k 10 400 python3 benchmarks/run.py --workload $cell --seed $seed \
    --seconds $seconds --trace $trace --out ${base} > ${base}.out 2> ${base}.err
  echo "rc=$? $cell seed=$seed trace=$trace whole_run_s=$(( $(date +%s) - t_run ))"
  # what the run left behind, zombies too: the driver refuses a PR over one
  echo "left_behind=$(ps -eo pid,ppid,stat,nlwp,args | grep -c '[r]ay_tpu\|[b]enchmarks/\(run\|check\)\|[d]efunct')"
  ps -eo pid,ppid,stat,nlwp,etimes,args | grep '[r]ay_tpu\|[b]enchmarks/\(run\|check\)\|[d]efunct' | cut -c1-200
  grep "^\[bench" ${base}.err | tail -8 | cut -c1-300
  grep -v "^{" ${base}.out | tail -14 | cut -c1-400
  tail -1 ${base}.out | cut -c1-2500
  rm -rf ${base}/trace ${base}/train_storage
done

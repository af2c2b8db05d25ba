# PR 37, call 14 (second round): call 12's backlog pair read the change's capture 1.2 ms a step slower than the
# parent's (286 against 322 steps in 3 s) with the CPU clock gone, all of it idle inside `admit`. Which host span
# grew: parent (.parent) and change (.proof) traced at one seed under run_keep_pairs.py, which keeps every
# scheduler span of the capture with its arguments (and parses the capture once more inside the window: the
# runs' whole-window numbers are no measurement)
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr37/call14
mkdir -p $OUT
run() { # side dir workload seed
  local out=$OUT/$1_$3_seed$4_trace1.log T1=$SECONDS
  ( cd $2 && KEEP_PAIRS=1 BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_trace1 python3 $ROOT/benchmark/records/pr37/run_keep_pairs.py --workload $3 --seed $4 --seconds 45 --trace 1 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=1 rc=$? after $((SECONDS - T1)) s $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
keep=('sched_','serve_device_idle','serve_completed')
print(line['correct'], line['failed'], len(m), {k: round(v['value'],4) for k,v in m.items() if k.startswith(keep)})")"
  grep -h "launch_pairs:\|xplane_join:" $out | cut -c1-400
}
run change .proof gpt2s-serve-backlog 3700140101
run parent .parent gpt2s-serve-backlog 3700140101
du -sh $OUT

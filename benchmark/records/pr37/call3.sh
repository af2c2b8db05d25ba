# PR 37, call 3: call 2's three traced runs again (its runner died on the state kinds' first
# break_program call, which passes the model and no server): the change (the working tree) traced in
# the three cells whose programs hold no while
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr37/call3
mkdir -p $OUT
run() { # side dir workload seed trace [runner]
  local out=$OUT/$1_$3_seed$4_trace$5.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4 python3 ${6:--m benchmark.run} --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? after $((SECONDS - T1)) s $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], {k: round(v['value'],4) for k,v in m.items() if k.startswith(('sched_','serve_device_idle','serve_completed'))})")"
  grep -h "xplane_join:\|launch_pairs:" $out | cut -c1-900
  cat $OUT/$1_$3_$4/phase_seconds.jsonl
}
K=benchmark/records/pr37/run_keep_pairs.py
run change . kimi-serve-backlog 3700030101 1 $K
run change . dots3-serve-longctx 3700030202 1 $K
run change . sdar-serve-backlog 3700030303 1 $K
du -sh $OUT

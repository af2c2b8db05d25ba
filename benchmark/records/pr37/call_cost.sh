# PR 37, calls 4, 6 and 7: what the instrumentation costs, one cell a call ($1: gpt2s-serve-chat or
# gpt2s-serve-backlog; $2: the call's number; $3: 0 leaves the traced runs out): three seeds, at each the parent (.parent = git archive of
# b8cb645) and the change (.proof = git archive of the final tree) untraced, ring armed, as the driver
# runs them, and the change again with --trace 1; who runs first alternates. Both sides run under
# run_keep_pairs.py with KEEP_PAIRS=0: the engine's phase counters at the window's edges are kept
# (phase_seconds.jsonl), nothing else differs from `python3 -m benchmark.run`
set -u
ROOT=$PWD
CELL=$1
OUT=$ROOT/chiprun_out/pr37/call$2
mkdir -p $OUT
run() { # side dir seed trace
  local out=$OUT/$1_${CELL}_seed$3_trace$4.log T1=$SECONDS
  ( cd $2 && KEEP_PAIRS=0 BENCHMARK_RECORD_DIR=$OUT/$1_${CELL}_$3_trace$4 python3 $ROOT/benchmark/records/pr37/run_keep_pairs.py --workload $CELL --seed $3 --seconds 45 --trace $4 ) > $out 2> $out.err
  echo "$1 $CELL seed=$3 trace=$4 rc=$? after $((SECONDS - T1)) s $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
keep=('serve_tokens_per_s','req_latency','setup_s','sched_','serve_device_idle','serve_completed','loadgen')
print(line['correct'], line['failed'], {k: round(v['value'],4) for k,v in m.items() if k.startswith(keep)})")"
  grep -h "launch_pairs:" $out | cut -c1-700
}
S=37000${2}0
run parent .parent ${S}101 0
run change .proof ${S}101 0
[ "${3:-1}" = 1 ] && run change .proof ${S}101 1
run change .proof ${S}202 0
run parent .parent ${S}202 0
[ "${3:-1}" = 1 ] && run change .proof ${S}202 1
run parent .parent ${S}303 0
run change .proof ${S}303 0
[ "${3:-1}" = 1 ] && run change .proof ${S}303 1
for d in $OUT/*_trace?; do echo $d; cat $d/phase_seconds.jsonl; done
du -sh $OUT

# PR 37, call 8: the committed tree (.proof = git archive of the final index) traced: gpt2s-serve-chat at
# call 6's three seeds (its untraced runs of the same build are the other side of "untraced against
# --trace 1"), and sdar-serve-backlog once (call 3's line came from a reader that still counted the
# children of a sched_admit the capture's edge had cut)
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr37/call8
mkdir -p $OUT
run() { # workload seed keep_pairs
  local out=$OUT/change_$1_seed$2_trace1.log T1=$SECONDS
  ( cd .proof && KEEP_PAIRS=$3 BENCHMARK_RECORD_DIR=$OUT/change_$1_$2_trace1 python3 $ROOT/benchmark/records/pr37/run_keep_pairs.py --workload $1 --seed $2 --seconds 45 --trace 1 ) > $out 2> $out.err
  echo "change $1 seed=$2 trace=1 rc=$? after $((SECONDS - T1)) s $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], {k: round(v['value'],4) for k,v in m.items() if k.startswith(('sched_','serve_device_idle','serve_completed','loadgen'))})")"
  grep -h "launch_pairs:" $out | cut -c1-700
}
run gpt2s-serve-chat 3700060101 0
run gpt2s-serve-chat 3700060202 0
run gpt2s-serve-chat 3700060303 0
run sdar-serve-backlog 3700080404 1
python3 benchmark/records/pr37/read_records.py $OUT/*/*_trace1.json
du -sh $OUT

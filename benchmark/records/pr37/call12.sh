# PR 37, call 12 (second round, after the review): the CPU clock is out of _Phase. The two fixtures again
# by the recorder as committed; then parent (.parent = git archive of b8cb645 + BENCHMARK.json and
# benchmark/ of the tree, as the driver's traced runs) against change (.proof = git archive of the final
# tree), both --trace 1, at one seed a cell: what a profiler session costs the yardsticks there were
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr37/call12
mkdir -p $OUT
python3 benchmark/tests/record_pair_fixture.py > $OUT/record_pair_fixture.log 2> $OUT/record_pair_fixture.err
echo "record_pair_fixture rc=$? after $SECONDS s"; grep -v "^I0000\|^WARNING\|^W0000\|^xplane_join" $OUT/record_pair_fixture.log | cut -c1-1200
run() { # side dir workload seed
  local out=$OUT/$1_$3_seed$4_trace1.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_trace1 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace 1 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=1 rc=$? after $((SECONDS - T1)) s $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
keep=('sched_','serve_device_idle','serve_completed','loadgen','http_overhead')
print(line['correct'], line['failed'], len(m), {k: round(v['value'],4) for k,v in m.items() if k.startswith(keep)})")"
  grep -h "launch_pairs:\|xplane_join:" $out | cut -c1-500
}
run parent .parent gpt2s-serve-backlog 3700120101
run change .proof gpt2s-serve-backlog 3700120101
run change .proof gpt2s-serve-chat 3700120202
run parent .parent gpt2s-serve-chat 3700120202
run parent .parent kimi-serve-backlog 3700120303
run change .proof kimi-serve-backlog 3700120303
python3 benchmark/records/pr37/read_records.py $OUT/*/*_trace1.json
du -sh $OUT

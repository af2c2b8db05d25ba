# PR 45, call 3: the committed files alone (.proof = git archive of this PR's index, after /simplify) against the parent
# (.parent = git archive of 3db36ed): the claimed cell traced from .proof, then two more untraced pairs at equal seeds,
# who runs first alternating.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr45/call3
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
T0=$SECONDS
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_t$5.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_t$5 python3 $2/benchmark/records/pr45/run_with_stats.py --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  local rc=$?
  echo "== $1 $3 seed=$4 trace=$5 rc=$rc after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], len(m), {k: round(v['value'],4) for k,v in m.items()}, line['device'].get('memory_peak_bytes'), line['device'].get('busy_s'), line['device'].get('window_s'))")"
  grep "launch_pairs\|xplane_join: " $out | cut -c1-700
  cat $OUT/$1_$3_$4_t$5/stats_snapshots.jsonl 2>/dev/null | tail -n 2 | cut -c1-420
  return $rc
}
cp $ROOT/benchmark/records/pr45/run_with_stats.py $ROOT/.parent/benchmark/records/pr45/ 2>/dev/null || { mkdir -p $ROOT/.parent/benchmark/records/pr45; cp $ROOT/benchmark/records/pr45/run_with_stats.py $ROOT/.parent/benchmark/records/pr45/; }
run change $ROOT/.proof laguna-serve-mixed 4500030101 1
run parent $ROOT/.parent laguna-serve-mixed 4500030202 0
run change $ROOT/.proof laguna-serve-mixed 4500030202 0
run change $ROOT/.proof laguna-serve-mixed 2147483303 0
run parent $ROOT/.parent laguna-serve-mixed 2147483303 0
du -sh $OUT

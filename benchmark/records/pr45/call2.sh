# PR 45, call 2: one untraced pair at equal seeds in each other serving cell whose engine runs the changed file
# (kimi-serve-backlog: the state path with a chunk behind nearly every step; gpt2s-serve-backlog, sdar-serve-backlog:
# the branches that pass no hook), who runs first alternating (.parent = git archive of 3db36ed), with the /stats
# snapshots of the window; then a third untraced pair of the claimed cell.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr45/call2
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
T0=$SECONDS
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_t$5.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_t$5 python3 $ROOT/benchmark/records/pr45/run_with_stats.py --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  local rc=$?
  echo "== $1 $3 seed=$4 trace=$5 rc=$rc after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], len(m), {k: round(v['value'],4) for k,v in m.items()}, line['device'].get('memory_peak_bytes'), line['device'].get('busy_s'), line['device'].get('window_s'))")"
  cat $OUT/$1_$3_$4_t$5/stats_snapshots.jsonl 2>/dev/null | tail -n 2 | cut -c1-420
  return $rc
}
run parent $ROOT/.parent kimi-serve-backlog 4500020101 0
run change $ROOT kimi-serve-backlog 4500020101 0
run change $ROOT gpt2s-serve-backlog 4500020202 0
run parent $ROOT/.parent gpt2s-serve-backlog 4500020202 0
run parent $ROOT/.parent sdar-serve-backlog 4500020303 0
run change $ROOT sdar-serve-backlog 4500020303 0
run change $ROOT laguna-serve-mixed 2147482404 0
run parent $ROOT/.parent laguna-serve-mixed 2147482404 0
du -sh $OUT

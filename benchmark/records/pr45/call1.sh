# PR 45, call 1: the claimed cell and A.X-K1, pairs at equal seeds, who runs first alternating (.parent = git archive of
# 3db36ed), one traced run of each side in Laguna and of the change in A.X-K1, with the /stats snapshots of the window.
# The change's first run leads: a fault of the change on the chip ends the call there.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr45/call1
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
  grep "launch_pairs\|xplane_join: " $out | cut -c1-700
  cat $OUT/$1_$3_$4_t$5/stats_snapshots.jsonl 2>/dev/null | tail -n 2 | cut -c1-420
  return $rc
}
run change $ROOT laguna-serve-mixed 4500010101 0 || { tail -n 30 $OUT/change_laguna-serve-mixed_seed4500010101_t0.log.err; exit 1; }
run parent $ROOT/.parent laguna-serve-mixed 4500010101 0
run parent $ROOT/.parent laguna-serve-mixed 2147481202 0
run change $ROOT laguna-serve-mixed 2147481202 0
run change $ROOT laguna-serve-mixed 4500010303 1
run parent $ROOT/.parent laguna-serve-mixed 4500010303 1
run parent $ROOT/.parent axk1-serve-longctx 4500010404 0
run change $ROOT axk1-serve-longctx 4500010404 0
run change $ROOT axk1-serve-longctx 2147481505 0
run parent $ROOT/.parent axk1-serve-longctx 2147481505 0
run change $ROOT axk1-serve-longctx 4500010606 1
du -sh $OUT

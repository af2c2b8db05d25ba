# PR 44, call 1: the new pieces alone (kernels and grouped matmuls by name), that the PARENT (d6b9143 + this PR's
# benchmark files laid over it: .parent) fails at once on the new cell, and one traced sound run of the change.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr44/call1
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
T0=$SECONDS
python3 benchmark/records/pr44/pieces_sweep.py $OUT/pieces.jsonl > $OUT/pieces.log 2>&1
echo "== pieces rc=$? after $((SECONDS - T0)) s"; tail -n 3 $OUT/pieces.log | cut -c1-300
T1=$SECONDS
( cd $ROOT/.parent && python3 -m benchmark.run --workload axk1-serve-longctx --seed 4400010101 --seconds 45 --trace 0 ) > $OUT/parent_new_cell.log 2>&1
echo "== parent on the new cell rc=$? after $((SECONDS - T1)) s"; tail -n 4 $OUT/parent_new_cell.log | cut -c1-300
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_t$5.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_t$5 python3 $ROOT/benchmark/records/pr44/run_with_stats.py --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "== $1 $3 seed=$4 trace=$5 rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], len(m), {k: round(v['value'],4) for k,v in m.items()}, line['device'].get('memory_peak_bytes'), line['device'].get('busy_s'), line['device'].get('window_s'))")"
  grep "^compared\|^check:\|launch_pairs\|xplane_join: \|set-up: " $out | cut -c1-400
  cat $OUT/$1_$3_$4_t$5/stats_snapshots.jsonl 2>/dev/null | tail -n 2 | cut -c1-420
  tail -n 5 $out.err | cut -c1-300
}
run change $ROOT axk1-serve-longctx 4400010202 1
du -sh $OUT

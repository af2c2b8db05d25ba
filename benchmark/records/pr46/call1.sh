# PR 46, call 1: each prefill width alone (traced), then the claimed cell: pairs at equal seeds, who runs first
# alternating (.parent = git archive of e00e71d), one traced run of each side, with the /stats snapshots of the window.
# The change's first run leads: a fault of the change on the chip ends the call there.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr46/call1
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
T0=$SECONDS
python3 benchmark/records/pr46/prefill_widths_bench.py $OUT/prefill_widths.jsonl 4600010001 > $OUT/bench.log 2> $OUT/bench.log.err \
  || { tail -n 30 $OUT/bench.log.err; exit 1; }
cat $OUT/bench.log | cut -c1-900
echo "== bench done at $((SECONDS - T0)) s; cache entries: $(ls $ROOT/.jax_cache 2>/dev/null | wc -l)"
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_t$5.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_t$5 python3 $ROOT/benchmark/records/pr46/run_with_stats.py --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  local rc=$?
  echo "== $1 $3 seed=$4 trace=$5 rc=$rc after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], len(m), {k: round(v['value'],4) for k,v in m.items()}, line['device'].get('memory_peak_bytes'), line['device'].get('busy_s'), line['device'].get('window_s'))")"
  grep "set-up: \|launch_pairs\|xplane_join: " $out | cut -c1-400
  grep "prefill widths" $out.err | cut -c1-200
  cat $OUT/$1_$3_$4_t$5/stats_snapshots.jsonl 2>/dev/null | tail -n 2 | cut -c1-420
  return $rc
}
run change $ROOT sdar-serve-backlog 4600010101 0 || { tail -n 30 $OUT/change_sdar-serve-backlog_seed4600010101_t0.log.err; exit 1; }
run parent $ROOT/.parent sdar-serve-backlog 4600010101 0
run parent $ROOT/.parent sdar-serve-backlog 2147481601 0
run change $ROOT sdar-serve-backlog 2147481601 0
run change $ROOT sdar-serve-backlog 4600010303 1
run parent $ROOT/.parent sdar-serve-backlog 4600010303 1
echo "cache entries: $(ls $ROOT/.jax_cache 2>/dev/null | wc -l)"; ls -la $ROOT/.jax_cache | head -20
du -sh $OUT

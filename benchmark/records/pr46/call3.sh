# PR 46, call 3, from the COMMITTED files (.proof = git archive of this PR's index; .parent = git archive of e00e71d):
# two more pairs of the claimed cell (call 2's parent run read 7 % under its other three) and a pair in
# laguna-serve-mixed (a state decoder: the constructor's new lines run, no whole-prompt prefill), who runs first alternating, with the /stats snapshots of the window.
# A fault of the change on the chip ends the call at its first run.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr46/call3
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
T0=$SECONDS
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
run parent $ROOT/.parent sdar-serve-backlog 4600030101 0
run change $ROOT/.proof sdar-serve-backlog 4600030101 0 || { tail -n 30 $OUT/change_sdar-serve-backlog_seed4600030101_t0.log.err; exit 1; }
run change $ROOT/.proof sdar-serve-backlog 2147483003 0
run parent $ROOT/.parent sdar-serve-backlog 2147483003 0
run parent $ROOT/.parent laguna-serve-mixed 4600030202 0
run change $ROOT/.proof laguna-serve-mixed 4600030202 0
echo "cache entries: $(ls $ROOT/.jax_cache 2>/dev/null | wc -l)"
du -sh $OUT

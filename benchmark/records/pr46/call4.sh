# PR 46, call 4 (after the driver's refusal: gpt2s-serve-chat req_latency_p50_ms spread 9.22 ms with the change
# against a bound of 8.97 ms, the parent's 6.85): six pairs of gpt2s-serve-chat on six unlike seeds from the COMMITTED
# files (.proof = git archive of this PR's index; .parent = git archive of e00e71d), who runs first alternating,
# with the /stats snapshots of the window. Read: the spread of p50 / p95 on each side over its six runs.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr46/call4
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
print(line['correct'], line['failed'], len(m), {k: round(v['value'],4) for k,v in m.items()}, line['device'].get('memory_peak_bytes'))")"
  cat $OUT/$1_$3_$4_t$5/stats_snapshots.jsonl 2>/dev/null | tail -n 2 | cut -c1-900
  return $rc
}
W=gpt2s-serve-chat
run parent $ROOT/.parent $W 4600040101 0
run change $ROOT/.proof $W 4600040101 0 || { tail -n 30 $OUT/change_${W}_seed4600040101_t0.log.err; exit 1; }
run change $ROOT/.proof $W 2147480402 0
run parent $ROOT/.parent $W 2147480402 0
run parent $ROOT/.parent $W 4600040303 0
run change $ROOT/.proof $W 4600040303 0
run change $ROOT/.proof $W 1300040404 0
run parent $ROOT/.parent $W 1300040404 0
run parent $ROOT/.parent $W 4600040505 0
run change $ROOT/.proof $W 4600040505 0
run change $ROOT/.proof $W 2147483606 0
run parent $ROOT/.parent $W 2147483606 0
du -sh $OUT

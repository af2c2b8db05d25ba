# PR 38, call 3: the three-way table at equal seeds, so that the ledger's one number can be split:
# parent (.parent = git archive of 768dbfc), (a) alone (.a_only = .proof with pair_bound returning
# its pairs: the tile rule, every T x k row), (a) + (b) (.proof = git archive of this PR's index).
# Order: parent, a, change, change, a, parent. Then one traced run of the change with the engine's
# /stats kept (dsa_steps.txt, stats_snapshots.jsonl).
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr38/call3
mkdir -p $OUT
rm -rf .a_only && cp -r .proof .a_only
sed -i 's/    return bound if 2 \* bound <= pairs else pairs/    return pairs/' .a_only/distributed_tensorflow_example_tpu/ops/moe.py
grep -c "^    return pairs$" .a_only/distributed_tensorflow_example_tpu/ops/moe.py
run() { # side dir seed trace [runner]
  local out=$OUT/$1_seed$3_t$4.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_t$4 python3 ${5:--m benchmark.run} --workload dots3-serve-longctx --seed $3 --seconds 45 --trace $4 ) > $out 2> $out.err
  echo "== $1 seed=$3 trace=$4 rc=$? after $((SECONDS - T1)) s (call at $SECONDS s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
keep=('serve_tokens_per_s','setup_s','serve_compile_s','export_s','serve_prefill_chunk_ms','sched_decode_step_ms','sched_prefill_share','serve_device_idle','serve_moe','serve_dsa','serve_window','serve_hbm','serve_completed')
print(line['correct'], line['failed'], {k: round(v['value'],4) for k,v in m.items() if k.startswith(keep)})")"
}
S1=3800030101; S2=2147483701
run parent .parent $S1 0
run a_only .a_only $S1 0
run change .proof $S1 0
run change .proof $S2 0
run a_only .a_only $S2 0
run parent .parent $S2 0
run change_traced .proof 3800030303 1 benchmark/records/pr38/run_with_stats.py
cat $OUT/change_traced_3800030303_t1/stats_snapshots.jsonl | cut -c1-500
grep -n "prefill_chunk:" -A 12 $OUT/change_traced_3800030303_t1/dsa_steps.txt | cut -c1-260
grep -n "^decode:" -A 6 $OUT/change_traced_3800030303_t1/dsa_steps.txt | cut -c1-200
du -sh $OUT

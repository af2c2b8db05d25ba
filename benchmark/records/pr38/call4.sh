# PR 38, call 4: the final tree (.proof = git archive of the index after /simplify) against the
# parent (.parent = git archive of 768dbfc), equal seeds, who runs first alternating: the claimed
# cell at two more seeds and a traced parent beside call 3's traced change (the same seed); then one
# pair of each other configuration that runs moe_dropless or the engine's chunk path (Kimi, SDAR:
# byte-equal programs) and GPT-2's backlog (the engine alone); the layer bench on the final module.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr38/call4
mkdir -p $OUT
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_t$5.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_t$5 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "== $1 $3 seed=$4 trace=$5 rc=$? after $((SECONDS - T1)) s (call at $SECONDS s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
keep=('serve_tokens_per_s','setup_s','serve_compile_s','export_s','serve_prefill_chunk_ms','sched_decode_step_ms','sched_prefill_share','serve_device_idle','serve_moe','serve_dsa','serve_window','serve_hbm')
print(line['correct'], line['failed'], {k: round(v['value'],4) for k,v in m.items() if k.startswith(keep)})")"
}
run change .proof dots3-serve-longctx 1900040101 0
run parent .parent dots3-serve-longctx 1900040101 0
run parent .parent dots3-serve-longctx 3800040202 0
run change .proof dots3-serve-longctx 3800040202 0
run parent .parent dots3-serve-longctx 3800030303 1
grep -n "prefill_chunk:" -A 6 $OUT/parent_dots3-serve-longctx_3800030303_t1/dsa_steps.txt | cut -c1-200
run parent .parent kimi-serve-backlog 3800040404 0
run change .proof kimi-serve-backlog 3800040404 0
run change .proof sdar-serve-backlog 3800040505 0
run parent .parent sdar-serve-backlog 3800040505 0
run parent .parent gpt2s-serve-backlog 3800040606 0
run change .proof gpt2s-serve-backlog 3800040606 0
( cd .proof && python3 benchmark/records/pr38/layer_bench.py $OUT/layer_bench.jsonl ) > $OUT/layer.log 2>&1
echo "layer rc=$?"; python3 - <<'PY'
import json
for l in open("chiprun_out/pr38/call4/layer_bench.jsonl"):
    r = json.loads(l)
    print(r["variant"], r["tiles"].get("gate"), r["tiles"].get("down"), r["pairs"], r["bound"], r["held_pairs"], r["ms"], r["ragged_dot_ms"], r["max_abs_diff"])
PY
du -sh $OUT

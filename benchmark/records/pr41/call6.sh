# PR 41, call 6: the committed files alone (.proof = git archive of this PR's index) run the new cell traced, and one
# cell of each other configuration most at risk from the shared code, parent (.parent = git archive of 16ff970 with
# this PR's benchmark files laid over it) against change at equal seeds, who runs first alternating.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr41/call6
mkdir -p $OUT
T0=$SECONDS
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_t$5.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_t$5 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "== $1 $3 seed=$4 trace=$5 rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], {k: round(v['value'],4) for k,v in m.items()}, line['device'].get('memory_peak_bytes'), line['device'].get('busy_s'), line['device'].get('window_s'))")"
}
run change .proof laguna-serve-mixed 4100050101 1
grep "^compared\|^check\|launch_pairs" $OUT/change_laguna-serve-mixed_seed4100050101_t1.log | cut -c1-300
head -n 5 $OUT/change_laguna-serve-mixed_4100050101_t1/gqa_steps.txt; grep -n "^prefill_chunk" -A4 $OUT/change_laguna-serve-mixed_4100050101_t1/gqa_steps.txt
# set C: six more sound runs on unlike seeds, from the committed files (set A's 2.55 % was its machine's)
for seed in 4100060101 1500060202 2147499999 800060404 3700060505 77060606; do run setc .proof laguna-serve-mixed $seed 0; done
python3 - <<PY
import json, glob, statistics
vals = sorted(json.load(open(f))["line"]["metrics"]["serve_tokens_per_s"]["value"] for f in glob.glob("$OUT/setc_*/*_trace0.json"))
q = statistics.quantiles(vals, n=4)
print("set C serve_tokens_per_s", vals, "median", statistics.median(vals), "iqr/median %", 100 * (q[2] - q[0]) / statistics.median(vals))
PY
run parent .parent dots3-serve-longctx 4100050202 0
run change .proof dots3-serve-longctx 4100050202 0
run change .proof kimi-serve-backlog 4100050303 0
run parent .parent kimi-serve-backlog 4100050303 0
du -sh $OUT

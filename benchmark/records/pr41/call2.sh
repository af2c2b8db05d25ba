# PR 41, calls 2 and 3: one set of six sound runs of the cell on unlike seeds (SET=a: call 2, SET=b: call 3),
# untraced, as the driver runs them; each run's record kept (runs/*.json). RUNNER=benchmark/records/pr41/run_with_stats.py
# (set b) keeps the engine's /stats at the window's edges beside them: nothing of a run changes.
set -u
ROOT=$PWD
SET=${SET:-a}
OUT=$ROOT/chiprun_out/pr41/set_$SET
mkdir -p $OUT
export BENCHMARK_RECORD_DIR=$OUT/runs
if [ $SET = a ]; then SEEDS="4100020101 1700020202 2147483777 900020404 3900020505 41020606"
else SEEDS="4100030101 2600030202 2147490001 700030404 3300030505 123030606"; fi
T0=$SECONDS
for seed in $SEEDS; do
T1=$SECONDS
python3 ${RUNNER:--m benchmark.run} --workload laguna-serve-mixed --seed $seed --seconds 45 --trace 0 > $OUT/sound_${seed}_t0.log 2> $OUT/sound_${seed}_t0.err
echo "== seed $seed rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $OUT/sound_${seed}_t0.log | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['attempted'], line['failed'], {k: round(v['value'],4) for k,v in m.items()}, line['device'].get('memory_peak_bytes'))")"
grep "^compared\|^check\|^read served" $OUT/sound_${seed}_t0.log | cut -c1-200
grep "^request" $OUT/sound_${seed}_t0.log | head -n 3
if [ -f $BENCHMARK_RECORD_DIR/stats_snapshots.jsonl ]; then python3 - <<PY
import json
snaps=[r for r in map(json.loads, open("$BENCHMARK_RECORD_DIR/stats_snapshots.jsonl")) if "t" in r]
a,b=snaps[-2],snaps[-1]
print(" window:", {k:(b[k]-a[k]) for k in ("prefill_chunks","moe_whole_layers","decode_steps")}, {k: round(b["sched_phase_seconds"][k]-a["sched_phase_seconds"][k],3) for k in ("admit","dispatch","wait_logits","sample_emit","admit_launch","admit_read")})
PY
fi
done
python3 - <<PY
import json, glob, statistics
vals = sorted(json.load(open(f))["line"]["metrics"]["serve_tokens_per_s"]["value"] for f in glob.glob("$OUT/runs/*_trace0.json"))
q = statistics.quantiles(vals, n=4)
print("serve_tokens_per_s", vals, "median", statistics.median(vals), "iqr/median %", 100 * (q[2] - q[0]) / statistics.median(vals))
PY

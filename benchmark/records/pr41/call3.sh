# PR 41, call 3: set A spread 2.6 % (979-1014 tokens/s) and the runs differ all through the window by the same few per
# cent: is it the seed (its weights route otherwise) or the host? Two seeds of set A, the slowest and the fastest,
# each TWICE, alternating, with the engine's /stats kept (moe_whole_layers, sched_phase_seconds).
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr41/call3
mkdir -p $OUT
T0=$SECONDS
n=0
for seed in 1700020202 4100020101 1700020202 4100020101; do
n=$((n + 1))
T1=$SECONDS
BENCHMARK_RECORD_DIR=$OUT/run${n}_$seed python3 benchmark/records/pr41/run_with_stats.py --workload laguna-serve-mixed --seed $seed --seconds 45 --trace 0 > $OUT/run${n}_$seed.log 2> $OUT/run${n}_$seed.err
echo "== run $n seed $seed rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $OUT/run${n}_$seed.log | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['attempted'], line['failed'], {k: round(v['value'],4) for k,v in m.items()})")"
python3 - <<PY
import json
rows=[json.loads(l) for l in open("$OUT/run${n}_$seed/stats_snapshots.jsonl")]
snaps=[r for r in rows if "t" in r]
a,b=snaps[-2],snaps[-1]
print(" window edges:", {k:(b[k]-a[k]) for k in ("prefill_chunks","moe_bounded_layers","moe_whole_layers","decode_steps")}, "dt", round(b["t"]-a["t"],2))
print(" phase seconds in window:", {k: round(b["sched_phase_seconds"][k]-a["sched_phase_seconds"][k],3) for k in b["sched_phase_seconds"]})
print(" total moe_whole_layers", b["moe_whole_layers"], "of", b["moe_whole_layers"]+b["moe_bounded_layers"], "snapshots", len(snaps))
PY
done

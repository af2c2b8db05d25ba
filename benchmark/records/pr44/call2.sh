# PR 44, call 2: set A of six untraced sound runs of axk1-serve-longctx on six unlike seeds (one call, one machine:
# what the driver's admission does), then the fp8 control cold on two seeds.
set -u
ROOT=$PWD
SRC=${SRC:-$ROOT}
SET=${SET:-a}
OUT=$ROOT/chiprun_out/pr44/call2_set$SET
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
T0=$SECONDS
for seed in ${SEEDS:-4400020101 1500020202 2147480303 800020404 3700020505 77020606}; do
  T1=$SECONDS
  ( cd $SRC && BENCHMARK_RECORD_DIR=$OUT/runs python3 benchmark/records/pr44/run_with_stats.py --workload axk1-serve-longctx --seed $seed --seconds 45 --trace 0 ) > $OUT/set${SET}_$seed.log 2> $OUT/set${SET}_$seed.err
  echo "== set $SET seed=$seed rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $OUT/set${SET}_$seed.log | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], line['attempted'], {k: round(v['value'],3) for k,v in m.items()}, line['device'].get('memory_peak_bytes'))")"
  grep "^compared served\|^check:" $OUT/set${SET}_$seed.log | cut -c1-300
done
python3 - <<PY
import json, glob, statistics
v = sorted(json.load(open(f))["line"]["metrics"]["serve_tokens_per_s"]["value"] for f in glob.glob("$OUT/runs/*_trace0.json"))
q = statistics.quantiles(v, n=4)
print("set $SET:", [round(x, 1) for x in v], "median", round(statistics.median(v), 1), "spread %", round(100 * (q[2] - q[0]) / statistics.median(v), 3))
PY
if [ "${CONTROL:-1}" = 1 ]; then
T1=$SECONDS
( cd $SRC && python3 benchmark/records/pr44/control.py 4400020901 1100020902 ) > $OUT/control.log 2>&1
echo "== control: rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s)"
grep "^{\|^compared\|^read served\|Error" $OUT/control.log | cut -c1-400
fi
du -sh $OUT

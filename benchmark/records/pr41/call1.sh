# PR 41, call 1: the attention forms and the grouped matmuls alone, the parent on the new cell
# (.parent = git archive of 16ff970 with this PR's benchmark files laid over it: it has to fail at
# once), then one traced sound run of the cell on this tree.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr41/call1
mkdir -p $OUT
T0=$SECONDS
python3 benchmark/records/pr41/attn_sweep.py $OUT/attn_sweep.jsonl > $OUT/attn_sweep.log 2>&1
echo "== attn_sweep rc=$? after $((SECONDS - T0)) s"; cut -c1-330 $OUT/attn_sweep.jsonl
T0=$SECONDS
python3 experiments/flash_sweep.py ragged $OUT/ragged_laguna_sweep.jsonl laguna > $OUT/ragged.log 2>&1
echo "== ragged rc=$? after $((SECONDS - T0)) s"
python3 - <<PY
import json
for ln in open("$OUT/ragged_laguna_sweep.jsonl"):
    r = json.loads(ln)
    print(r.get("m"), r.get("k"), r.get("n"), r.get("grouped"), r.get("skew"), r.get("tiling"), r.get("ms"), r.get("max_abs_diff"), (r.get("error") or "")[:120])
PY
T0=$SECONDS
( cd .parent && timeout 300 python3 -m benchmark.run --workload laguna-serve-mixed --seed 4100010001 --seconds 45 --trace 0 ) > $OUT/parent_new_cell.log 2>&1
echo "== parent on the new cell rc=$? after $((SECONDS - T0)) s"; tail -n 4 $OUT/parent_new_cell.log | cut -c1-300
T0=$SECONDS
BENCHMARK_RECORD_DIR=$OUT/sound_t1 python3 -m benchmark.run --workload laguna-serve-mixed --seed 4100010102 --seconds 45 --trace 1 > $OUT/sound_4100010102_t1.log 2> $OUT/sound_4100010102_t1.err
echo "== sound traced rc=$? after $((SECONDS - T0)) s"
grep -v "^request\|^compared\|^set-up" $OUT/sound_4100010102_t1.log | tail -n 12 | cut -c1-3000
grep "^compared\|^set-up\|^check\|^read" $OUT/sound_4100010102_t1.log
tail -n 5 $OUT/sound_4100010102_t1.err | cut -c1-400
head -n 80 $OUT/sound_t1/gqa_steps.txt | cut -c1-260
du -sh $OUT

# PR 38, call 1: the grouped matmul alone at dots3's widths (the sweep), then the chunk's expert
# layer alone under the parent's program, (a) alone and (a) + (b) with each combine, at three
# forced tile pairs (the rule is written from these rows).
set -u
OUT=chiprun_out/pr38/call1
mkdir -p $OUT
python3 experiments/flash_sweep.py ragged $OUT/ragged_dots3_sweep.jsonl dots3 > $OUT/sweep.log 2>&1
echo "sweep rc=$? rows=$(wc -l < $OUT/ragged_dots3_sweep.jsonl)"
python3 benchmark/records/pr38/layer_bench.py $OUT/layer_bench.jsonl \
  128,5120,512/128,1536,1280 128,2560,768/128,1536,1024 128,1280,1536/128,768,2560 > $OUT/layer.log 2>&1
echo "layer rc=$? rows=$(wc -l < $OUT/layer_bench.jsonl)"
python3 - <<'PY'
import json
for l in open("chiprun_out/pr38/call1/ragged_dots3_sweep.jsonl"):
    r = json.loads(l)
    print(r["k"], r["n"], r["m"], "skew" if r["skew"] else "unif", r["tiling"], r.get("ms"), r.get("max_abs_diff"), r.get("error", "")[:80])
for l in open("chiprun_out/pr38/call1/layer_bench.jsonl"):
    r = json.loads(l)
    print(r["variant"], r["forced"], r["tiles"].get("gate"), r["tiles"].get("down"), r["pairs"], r["bound"], r["held_pairs"], r["ms"], r["ragged_dot_ms"], r["max_abs_diff"])
PY

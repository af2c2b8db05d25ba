# PR 38, call 2: the chunk's expert layer alone again, under the rule call 1's rows wrote
# (128,5120,512 / 128,1536,1280), near-uniform routing, with the three-pass one-hot combine added.
set -u
OUT=chiprun_out/pr38/call2
mkdir -p $OUT
python3 benchmark/records/pr38/layer_bench.py $OUT/layer_bench.jsonl > $OUT/layer.log 2>&1
echo "layer rc=$? rows=$(wc -l < $OUT/layer_bench.jsonl)"
python3 - <<'PY'
import json
for l in open("chiprun_out/pr38/call2/layer_bench.jsonl"):
    r = json.loads(l)
    print(r["variant"], r["tiles"].get("gate"), r["tiles"].get("down"), r["pairs"], r["bound"], r["held_pairs"], r["ms"], r["ragged_dot_ms"], r["max_abs_diff"])
    print("   ", [(n[:28], s) for n, s in r["top_ops"][:6]])
PY

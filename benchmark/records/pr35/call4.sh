# PR 35, call 4: (1) the selected-attention kernel at 256, 512 and 1,024 latent rows a grid step (the
# pieces bench); the best by the sum over the four contexts becomes `key_tile`'s default on this
# machine's copy (the tree gets the same edit by hand after the call); (2) six sound runs of the
# cell on unlike seeds with it: the spread of serve_tokens_per_s against half the 4 % bound.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr35/call4
mkdir -p $OUT
export BENCHMARK_RECORD_DIR=$OUT/runs
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
T0=$SECONDS
python3 benchmark/records/pr35/pieces_bench.py $OUT/pieces.jsonl > $OUT/pieces.log 2>&1
echo "== pieces: rc=$? after $((SECONDS - T0)) s"; grep "^{\|Error" $OUT/pieces.log | grep "pallas\|kernel\|Error" | cut -c1-200
BEST=$(python3 - $OUT/pieces.jsonl <<'PY'
import json, sys
ms = {}
for ln in open(sys.argv[1]):
    d = json.loads(ln)
    if d["piece"].startswith("masked_attention_pallas"):
        tile = d["piece"].rsplit("_", 1)[1]
        tile = 512 if tile == "pallas" else int(tile)
        ms[tile] = ms.get(tile, 0.0) + d["ms"]
print(min(ms, key=ms.get) if ms else 512)
PY
)
echo "== key_tile: $BEST"
M=distributed_tensorflow_example_tpu/ops/mla.py
sed -i "s/                                 key_tile: int = 512,/                                 key_tile: int = $BEST,/" $M
grep -c "key_tile: int = $BEST," $M
for seed in 3500040101 2147483693 1700040303 900040404 3900040505 41040606; do
T1=$SECONDS
python3 -m benchmark.run --workload dots3-serve-longctx --seed $seed --seconds 45 --trace 0 > $OUT/sound_${seed}_t0.log 2>&1
echo "== sound $seed: rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s)"
grep -v "$F" $OUT/sound_${seed}_t0.log | grep "compared\|check:\|request [0-9]\|^{\|Error\|error" | cut -c1-700 | tail -n 8
done

# PR 35, call 3: the selected attention as a Pallas kernel (dsa_selected_attn) against the XLA tile
# loop, and the selection's counting passes held to the context, alone at the cell's shapes; then one
# traced run of the cell with both.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr35/call3
mkdir -p $OUT
export BENCHMARK_RECORD_DIR=$OUT/runs
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
T0=$SECONDS
python3 benchmark/records/pr35/pieces_bench.py $OUT/pieces.jsonl > $OUT/pieces.log 2>&1
echo "== pieces: rc=$? after $((SECONDS - T0)) s"; grep "^{\|Error" $OUT/pieces.log | cut -c1-200; grep -v "^{" $OUT/pieces.log | grep -v Warn | tail -n 12 | cut -c1-300
T0=$SECONDS
python3 -m benchmark.run --workload dots3-serve-longctx --seed 3500030101 --seconds 45 --trace 1 > $OUT/sound_3500030101_t1.log 2>&1
echo "== sound traced: rc=$? after $((SECONDS - T0)) s"
grep -v "$F" $OUT/sound_3500030101_t1.log | grep "set-up\|compared\|read served\|check:\|request [0-9]\|^{\|Error\|error" | cut -c1-3000 | tail -n 30
head -n 12 $OUT/runs/dsa_steps.txt | cut -c1-300
grep -n "prefill_chunk:" -A 40 $OUT/runs/dsa_steps.txt | cut -c1-300

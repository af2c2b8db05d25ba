# PR 35, call 2: (1) the new pieces alone at the cell's shapes (XLA forms), by context; (2) one traced
# sound run, its attribution kept beside the records (call 1's traced reading died in
# state_steps.describe under BENCHMARK_KEEP_TRACE: that variable is not set again); (3) how often the
# bfloat16 program selects another set than the float32 reference, over a 6-chunk prompt.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr35/call2
mkdir -p $OUT
export BENCHMARK_RECORD_DIR=$OUT/runs
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
T0=$SECONDS
python3 benchmark/records/pr35/pieces_bench.py $OUT/pieces.jsonl > $OUT/pieces.log 2>&1
echo "== pieces: rc=$? after $((SECONDS - T0)) s"; grep "^{\|Error" $OUT/pieces.log | cut -c1-200
T0=$SECONDS
python3 -m benchmark.run --workload dots3-serve-longctx --seed 3500020101 --seconds 45 --trace 1 > $OUT/sound_3500020101_t1.log 2>&1
echo "== sound traced: rc=$? after $((SECONDS - T0)) s"
grep -v "$F" $OUT/sound_3500020101_t1.log | grep "set-up\|compared\|read served\|check:\|request [0-9]\|^{\|Error\|error" | cut -c1-3000 | tail -n 30
head -n 100 $OUT/runs/dsa_steps.txt | cut -c1-300
T0=$SECONDS
python3 benchmark/records/pr35/selection_flips.py 3500020202 6 > $OUT/selection_flips.log 2>&1
echo "== selection flips: rc=$? after $((SECONDS - T0)) s"; grep "^{\|Error" $OUT/selection_flips.log | cut -c1-600

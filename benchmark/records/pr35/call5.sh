# PR 35, call 5: the limits' other readings at the cell's own size: the fp8 control (cold, two
# seeds) and each planted fault (benchmark/planted_dsa.py), a 10 s window each (the ramp, the drain
# and the check are the cell's; only `correct` is read).
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr35/call5
mkdir -p $OUT
export BENCHMARK_RECORD_DIR=$OUT/runs
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
T0=$SECONDS
n=0
for fault in selection_dropped window_not_applied index_keys_stale rope_left_off_k_pe; do
n=$((n + 1))
seed=$((3500050000 + 101 * n))
T1=$SECONDS
python3 -m benchmark.planted_dsa --fault $fault --workload dots3-serve-longctx --seed $seed --seconds 10 --trace 0 > $OUT/${fault}_${seed}.log 2>&1
echo "== $fault seed $seed: rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s)"
grep -v "$F" $OUT/${fault}_${seed}.log | grep "compared\|read served\|check:\|^{\|Error\|error" | cut -c1-500 | tail -n 8
done
T1=$SECONDS
python3 benchmark/records/pr35/control.py 3500050901 1100050902 > $OUT/control.log 2>&1
echo "== control: rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s)"
grep "^{\|compared\|read served\|Error" $OUT/control.log | cut -c1-400

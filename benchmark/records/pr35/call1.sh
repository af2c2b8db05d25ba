# PR 35, call 1: (1) the new cell with this PR's benchmark files over the PARENT's program (.parent =
# git archive of 10211e5 + BENCHMARK.json + benchmark/): it has to stop at once, with another exit
# code than 0; (2) one traced sound run of the cell on the change, the capture's attribution kept.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr35/call1
mkdir -p $OUT
export BENCHMARK_RECORD_DIR=$ROOT/chiprun_out/pr35/runs
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
T0=$SECONDS
( cd .parent && python3 -m benchmark.run --workload dots3-serve-longctx --seed 3500010001 --seconds 45 --trace 0 ) > $OUT/parent_new_cell.log 2>&1
echo "== parent in the new cell: rc=$? after $((SECONDS - T0)) s"; grep -v "$F" $OUT/parent_new_cell.log | tail -n 4 | cut -c1-300
T0=$SECONDS
BENCHMARK_KEEP_TRACE=$OUT/trace python3 -m benchmark.run --workload dots3-serve-longctx --seed 3500010102 --seconds 45 --trace 1 > $OUT/sound_3500010102_t1.log 2>&1
echo "== sound traced: rc=$? after $((SECONDS - T0)) s"
grep -v "$F" $OUT/sound_3500010102_t1.log | grep "set-up\|compared\|read served\|check:\|memory_stats\|request [0-9]\|^{\|Error\|error" | cut -c1-1500 | tail -n 40
cp $OUT/trace/dsa_steps.txt $OUT/dsa_steps_seed3500010102.txt 2>/dev/null
cp $OUT/trace/state_steps.txt $OUT/state_steps_seed3500010102.txt 2>/dev/null
rm -rf $OUT/trace
head -n 90 $OUT/dsa_steps_seed3500010102.txt | cut -c1-260

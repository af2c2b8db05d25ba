# PR 41, call 5 (its outputs are under chiprun_out/pr41/call4: the script was numbered 4 when it ran): the limits' other readings at the cell's own size: each planted fault
# (benchmark/planted_gqa.py), a 10 s window each (the ramp, the drain and the check are the cell's; only
# `correct` is read), and the fp8 control (cold, two seeds).
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr41/call4
mkdir -p $OUT
export BENCHMARK_RECORD_DIR=$OUT/runs
T0=$SECONDS
n=0
for fault in ${FAULTS:-group_map_wrong window_not_applied rope_on_whole_head head_gate_dropped routed_scale_left_off}; do
n=$((n + 1))
seed=$((4100040000 + 101 * n))
T1=$SECONDS
python3 -m benchmark.planted_gqa --fault $fault --workload laguna-serve-mixed --seed $seed --seconds 10 --trace 0 > $OUT/${fault}_${seed}.log 2> $OUT/${fault}_${seed}.err
echo "== $fault seed $seed: rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s)"
grep "^compared\|^read served\|^check:\|^{" $OUT/${fault}_${seed}.log | cut -c1-400 | tail -n 8
done
T1=$SECONDS
python3 benchmark/records/pr41/control.py 4100040901 1100040902 > $OUT/control.log 2>&1
echo "== control: rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s)"
grep "^{\|compared\|read served\|Error" $OUT/control.log | cut -c1-400

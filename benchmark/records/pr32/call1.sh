set -x
mkdir -p chiprun_out/pr32/call1
export BENCHMARK_RECORD_DIR=chiprun_out/pr32/runs
BENCHMARK_KEEP_TRACE=chiprun_out/pr32/call1/trace python3 -m benchmark.run --workload kimi-serve-backlog --seed 3200000101 --seconds 45 --trace 1 > chiprun_out/pr32/call1/run_trace1.log 2>&1
echo "rc=$?"
tail -c 6000 chiprun_out/pr32/call1/run_trace1.log
python3 experiments/flash_sweep.py ragged chiprun_out/pr32/ragged_wide.jsonl wide > chiprun_out/pr32/call1/ragged.log 2>&1
echo "ragged rc=$?"
tail -5 chiprun_out/pr32/call1/ragged.log

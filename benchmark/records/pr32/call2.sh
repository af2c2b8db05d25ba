set -x
mkdir -p chiprun_out/pr32/call2
export BENCHMARK_RECORD_DIR=chiprun_out/pr32/runs
BENCHMARK_KEEP_TRACE=chiprun_out/pr32/call2/trace python3 -m benchmark.run --workload kimi-serve-backlog --seed 3200000202 --seconds 45 --trace 1 > chiprun_out/pr32/call2/run_seed3200000202_trace1.log 2>&1
echo "rc=$?"; grep -v "BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$" chiprun_out/pr32/call2/run_seed3200000202_trace1.log | tail -c 5000
python3 -m benchmark.run --workload kimi-serve-backlog --seed 3200000303 --seconds 45 --trace 0 > chiprun_out/pr32/call2/run_seed3200000303_trace0.log 2>&1
echo "rc=$?"; grep -v "BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$" chiprun_out/pr32/call2/run_seed3200000303_trace0.log | tail -c 3000
python3 - > chiprun_out/pr32/call2/control.log 2>&1 <<'PY'
import argparse, json
from benchmark import run as bench_run
from benchmark.manifest import ROOT, Manifest
for seed in (3200010001, 3200010002):
    ns = argparse.Namespace(workload="kimi-serve-backlog", seed=seed, seconds=45.0, trace=0, rehearse=0)
    env = bench_run.Env(Manifest(ROOT), ns)
    try:
        compared = env.manifest.kind(env.traffic).control(env)
        print("CONTROL", seed, json.dumps(compared), flush=True)
    finally:
        env.cleanup()
PY
echo "control rc=$?"; tail -c 2500 chiprun_out/pr32/call2/control.log

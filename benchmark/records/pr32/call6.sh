mkdir -p chiprun_out/pr32/call6
export BENCHMARK_RECORD_DIR=chiprun_out/pr32/runs
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
run() { local name=$1; shift
  python3 -m "$@" > chiprun_out/pr32/call6/$name.log 2>&1
  echo "== $name rc=$?"; grep -v "$F" chiprun_out/pr32/call6/$name.log | grep "compared\|read served\|check:\|^{\|Error" | cut -c1-700
}
run planted_dropped_3200061001 benchmark.planted_state --fault state_dropped_at_chunk --workload kimi-serve-backlog --seed 3200061001 --seconds 45 --trace 0
run planted_notzeroed_3200061002 benchmark.planted_state --fault slot_not_zeroed --workload kimi-serve-backlog --seed 3200061002 --seconds 45 --trace 0
run sound_3200060101_t0 benchmark.run --workload kimi-serve-backlog --seed 3200060101 --seconds 45 --trace 0
run sound_3200060202_t0 benchmark.run --workload kimi-serve-backlog --seed 3200060202 --seconds 45 --trace 0
run sound_3200060303_t0 benchmark.run --workload kimi-serve-backlog --seed 3200060303 --seconds 45 --trace 0
python3 - > chiprun_out/pr32/call6/control.log 2>&1 <<'PY'
import argparse, json
from benchmark import run as bench_run
from benchmark.manifest import ROOT, Manifest
for seed in (3200060901, 3200060902):
    ns = argparse.Namespace(workload="kimi-serve-backlog", seed=seed, seconds=45.0, trace=0, rehearse=0)
    env = bench_run.Env(Manifest(ROOT), ns)
    try:
        print("CONTROL", seed, json.dumps(env.manifest.kind(env.traffic).control(env)), flush=True)
    finally:
        env.cleanup()
PY
echo "control rc=$?"; grep "CONTROL\|read served" chiprun_out/pr32/call6/control.log

# (As it ran. The kernel lost: ops/pallas/kda_step.py, its branch in models/decoder.py and
# call7/kda_step_bench.py, which failed at import and measured nothing, were deleted after this call.)
# PR 32, call 7 (review round): (1) KDA's one-token update, XLA form against the Pallas kernel, at
# the cell's shape; (2) a traced run of the cell with the XLA form in the kernel's place; (3) the
# experts each token is routed to, program against reference; (4) two planted faults, each with 48
# checked requests drawn among those within planted_state.REACH of the fault, so that a request's
# gap can be read against where the fault lay. The faults run the form (1) decides for: the XLA
# form if the kernel saves under 1 ms a step (predictions.md), else the kernel.
mkdir -p chiprun_out/pr32/call7
O=chiprun_out/pr32/call7
export BENCHMARK_RECORD_DIR=chiprun_out/pr32/runs
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
python3 benchmark/records/pr32/call7/kda_step_bench.py > $O/kda_step_bench.log 2>&1; echo "== bench rc=$?"; grep -v Warning $O/kda_step_bench.log | cut -c1-300 | tail -50
cat > $O/diag.py <<'PY'
import sys
from benchmark import planted_state, run as bench_run
fault, seed, trace = sys.argv[1:4]
def hook(env):
    env.traffic["check_requests"] = 48
    if fault != "none":
        planted_state.hook_for(fault)(env)
rc = bench_run.main(["--workload", "kimi-serve-backlog", "--seed", seed, "--seconds", "45", "--trace", trace], env_hook=hook)
if planted_state.DROPPED:
    print(f"planted: state dropped at {planted_state.DROPPED}", file=sys.stderr, flush=True)
sys.exit(rc)
PY
run() { local name=$1; shift
  PYTHONPATH=. python3 $O/diag.py "$@" > $O/$name.log 2>&1
  echo "== $name rc=$?"; grep -v "$F" $O/$name.log | grep "compared\|read served\|check:\|^{\|Error" | cut -c1-700
}
D=distributed_tensorflow_example_tpu/models/decoder.py
cp $D $O/decoder.py.kernel
sed -i 's/if kernels and kda_kernel.step_tile_friendly(/if False and kda_kernel.step_tile_friendly(/' $D
test "$(grep -c 'if False and kda_kernel' $D)" = 1 || { echo "patch failed"; exit 1; }
BENCHMARK_KEEP_TRACE=$O/trace run xla_state_update_sound_3200070202_t1 none 3200070202 1
cp $O/trace/state_steps.txt $O/state_steps_xla_seed3200070202.txt 2>/dev/null; rm -rf $O/trace
head -12 $O/state_steps_xla_seed3200070202.txt | cut -c1-200
python3 - $O/kda_step_bench.log <<'PY' && { cp $O/decoder.py.kernel $D; echo "== the kernel saves 1 ms a step or more: the faults run the kernel"; } || echo "== the kernel saves under 1 ms a step: the faults run the XLA form"
import json, sys
ms = {}
for ln in open(sys.argv[1]):
    if ln.startswith('{"kda_step"'):
        d = json.loads(ln)
        ms[d["kda_step"]] = min(ms.get(d["kda_step"], 1e9), d["ms_4_layers"])
print("kda_step, best of two, ms a step:", ms)
sys.exit(0 if ms["xla"] - ms["pallas"] >= 1.0 else 1)
PY
rm -f $O/decoder.py.kernel
python3 benchmark/records/pr32/call7/router_flips.py 3200070001 > $O/router_flips.log 2>&1; echo "== flips rc=$?"; grep "^{\|Error" $O/router_flips.log | cut -c1-900
run diag_notzeroed_3200071002 slot_not_zeroed 3200071002 0
run diag_dropped_seeded_3200071003 state_dropped_at_seeded_chunk 3200071003 0

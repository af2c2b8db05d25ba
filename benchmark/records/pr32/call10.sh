# PR 32, call 10 (after the driver's refusal: serve_tokens_per_s spread 7.3 % over seeds): every seed
# now posts the mix's lengths in one order (kinds/serve_state.same_work). Six sound runs on unlike
# seeds, then a traced one if the time is left. Predicted (PERF.md 6): spread under 1.5 %.
mkdir -p chiprun_out/pr32/call10
export BENCHMARK_RECORD_DIR=chiprun_out/pr32/call10/runs
T0=$SECONDS
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
run() { local name=$1; shift
  python3 -m "$@" > chiprun_out/pr32/call10/$name.log 2>&1
  echo "== $name rc=$? at $((SECONDS - T0)) s"; grep -v "$F" chiprun_out/pr32/call10/$name.log | grep "compared\|check:\|^{\|Error" | cut -c1-900
}
for seed in 3200100101 2147483659 1600100303 800100404 3200100505 40100606; do
run sound_${seed}_t0 benchmark.run --workload kimi-serve-backlog --seed $seed --seconds 45 --trace 0
done
if [ $((SECONDS - T0)) -lt 1330 ]; then
run sound_3200100707_t1 benchmark.run --workload kimi-serve-backlog --seed 3200100707 --seconds 45 --trace 1
fi

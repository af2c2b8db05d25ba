# (Ran at the second try, 21:37 UTC; the first found no free chip in 180 s.)
# PR 32, call 11: four more sound runs of the one order of lengths, on four more seeds (with call
# 10's six: ten). The fourth starts only if the first three left it the time.
mkdir -p chiprun_out/pr32/call11
export BENCHMARK_RECORD_DIR=chiprun_out/pr32/call11/runs
T0=$SECONDS
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
run() { local name=$1; shift
  python3 -m "$@" > chiprun_out/pr32/call11/$name.log 2>&1
  echo "== $name rc=$? at $((SECONDS - T0)) s"; grep -v "$F" chiprun_out/pr32/call11/$name.log | grep "compared\|check:\|^{\|Error" | cut -c1-900
}
for seed in 2400110101 1200110202 3900110303 600110404; do
if [ $((SECONDS - T0)) -lt 610 ]; then
run sound_${seed}_t0 benchmark.run --workload kimi-serve-backlog --seed $seed --seconds 45 --trace 0
fi
done

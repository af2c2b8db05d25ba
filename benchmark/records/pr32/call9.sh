# (Ran at the seventh try, 19:05 UTC; six tries before it found no free chip.)
# PR 32, call 9 (review round, the final tree): the seeds of three of the kernel-form runs of calls
# 4 and 6 (3200060202 read 2,240.0 tokens/s there, the lowest; 3200040303 2,304.0; 3200040101
# 2,262.8) again with KDA's update in its XLA form: does the form move a seed's number, or the seed?
# The third starts only if the first two left it the time.
mkdir -p chiprun_out/pr32/call9
export BENCHMARK_RECORD_DIR=chiprun_out/pr32/call9/runs
T0=$SECONDS
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
run() { local name=$1; shift
  python3 -m "$@" > chiprun_out/pr32/call9/$name.log 2>&1
  echo "== $name rc=$? at $((SECONDS - T0)) s"; grep -v "$F" chiprun_out/pr32/call9/$name.log | grep "compared\|read served\|check:\|^{\|Error" | cut -c1-700
}
run sound_3200060202_t0 benchmark.run --workload kimi-serve-backlog --seed 3200060202 --seconds 45 --trace 0
run sound_3200040303_t0 benchmark.run --workload kimi-serve-backlog --seed 3200040303 --seconds 45 --trace 0
if [ $((SECONDS - T0)) -lt 410 ]; then
run sound_3200040101_t0 benchmark.run --workload kimi-serve-backlog --seed 3200040101 --seconds 45 --trace 0
fi

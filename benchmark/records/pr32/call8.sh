# PR 32, call 8 (review round, the final tree: KDA's one-token update in its XLA form, the checked
# sample drawn by the seed, a planted fault's among the requests within planted_state.REACH = 400
# tokens of it): two planted faults and three sound runs at the committed limits, fresh seeds.
mkdir -p chiprun_out/pr32/call8
export BENCHMARK_RECORD_DIR=chiprun_out/pr32/runs
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
run() { local name=$1; shift
  python3 -m "$@" > chiprun_out/pr32/call8/$name.log 2>&1
  echo "== $name rc=$?"; grep -v "$F" chiprun_out/pr32/call8/$name.log | grep "compared\|read served\|check:\|^{\|Error" | cut -c1-700
}
run planted_notzeroed_3200081002 benchmark.planted_state --fault slot_not_zeroed --workload kimi-serve-backlog --seed 3200081002 --seconds 45 --trace 0
run planted_dropped_seeded_3200081003 benchmark.planted_state --fault state_dropped_at_seeded_chunk --workload kimi-serve-backlog --seed 3200081003 --seconds 45 --trace 0
run sound_3200080101_t0 benchmark.run --workload kimi-serve-backlog --seed 3200080101 --seconds 45 --trace 0
run sound_3200080202_t0 benchmark.run --workload kimi-serve-backlog --seed 3200080202 --seconds 45 --trace 0
run sound_3200080303_t0 benchmark.run --workload kimi-serve-backlog --seed 3200080303 --seconds 45 --trace 0

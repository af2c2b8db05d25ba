mkdir -p chiprun_out/pr32/call4
export BENCHMARK_RECORD_DIR=chiprun_out/pr32/runs
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
run() { # name, module, extra args...
  local name=$1; shift
  python3 -m "$@" > chiprun_out/pr32/call4/$name.log 2>&1
  echo "== $name rc=$?"; grep -v "$F" chiprun_out/pr32/call4/$name.log | grep "compared\|read served\|check:\|^{\|Error" | cut -c1-1500
}
run sound_3200040101_t0 benchmark.run --workload kimi-serve-backlog --seed 3200040101 --seconds 45 --trace 0
BENCHMARK_KEEP_TRACE=chiprun_out/pr32/call4/trace run sound_3200040202_t1 benchmark.run --workload kimi-serve-backlog --seed 3200040202 --seconds 45 --trace 1
run sound_3200040303_t0 benchmark.run --workload kimi-serve-backlog --seed 3200040303 --seconds 45 --trace 0
run sound_3200040404_t0 benchmark.run --workload kimi-serve-backlog --seed 3200040404 --seconds 45 --trace 0
run sound_3200040505_t0 benchmark.run --workload kimi-serve-backlog --seed 3200040505 --seconds 45 --trace 0
run sound_3200040606_t0 benchmark.run --workload kimi-serve-backlog --seed 3200040606 --seconds 45 --trace 0
run planted_dropped_3200041001 benchmark.planted_state --fault state_dropped_at_chunk --workload kimi-serve-backlog --seed 3200041001 --seconds 45 --trace 0
run planted_notzeroed_3200041002 benchmark.planted_state --fault slot_not_zeroed --workload kimi-serve-backlog --seed 3200041002 --seconds 45 --trace 0
(cd .parent && timeout 120 python3 -m benchmark.run --workload kimi-serve-backlog --seed 1 --seconds 5 --trace 0 > ../chiprun_out/pr32/call4/parent_overlay_newcell.log 2>&1; echo "== parent overlay new cell rc=$?"; tail -3 ../chiprun_out/pr32/call4/parent_overlay_newcell.log | cut -c1-300)

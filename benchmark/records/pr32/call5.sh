mkdir -p chiprun_out/pr32/call5
export BENCHMARK_RECORD_DIR=$PWD/chiprun_out/pr32/runs_existing
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
run() { # dir, name, args...
  local dir=$1 name=$2; shift 2
  (cd $dir && python3 -m benchmark.run "$@" > $OLDPWD/chiprun_out/pr32/call5/$name.log 2>&1; echo "== $name rc=$?")
  grep -v "$F" chiprun_out/pr32/call5/$name.log | grep "compared\|^{\|Error" | cut -c1-1200
}
run .parent parent_sdar_1 --workload sdar-serve-backlog --seed 3200050101 --seconds 45 --trace 0
run . change_sdar_1 --workload sdar-serve-backlog --seed 3200050101 --seconds 45 --trace 0
run . change_sdar_2 --workload sdar-serve-backlog --seed 3200050202 --seconds 45 --trace 0
run .parent parent_sdar_2 --workload sdar-serve-backlog --seed 3200050202 --seconds 45 --trace 0
run .parent parent_gpt2s_backlog_1 --workload gpt2s-serve-backlog --seed 3200050303 --seconds 45 --trace 0
run . change_gpt2s_backlog_1 --workload gpt2s-serve-backlog --seed 3200050303 --seconds 45 --trace 0
run . change_gpt2s_backlog_2 --workload gpt2s-serve-backlog --seed 3200050404 --seconds 45 --trace 0
run .parent parent_gpt2s_backlog_2 --workload gpt2s-serve-backlog --seed 3200050404 --seconds 45 --trace 0
run .parent parent_overlay_gpt2s_train_t1 --workload gpt2s-train --seed 3200050505 --seconds 45 --trace 1

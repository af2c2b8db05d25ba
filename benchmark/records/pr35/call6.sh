# PR 35, call 6: the tree as git would commit it (.proof = git archive of the index) and the parent
# (.parent = git archive of 10211e5) under this PR's benchmark files: (1) the new cell on the parent
# stops at once; (2) one traced run of the new cell on the change; (3) an old cell traced on the
# parent with the new benchmark files (kimi-serve-backlog: the readers this PR added find nothing
# there and the line is whole); (4) that cell once on each side at one seed, no trace.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr35/call6
mkdir -p $OUT
F='BrokenPipe\|socketserver\|self\.\|http/server\|serving_http\|^---\|^$\|method()\|Exception occurred\|^Traceback\|During handling\|_ServerFault\|UserWarning\|warnings.warn'
run() { # name dir workload seed trace
  local T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/runs_$1 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $OUT/$1_$3_seed$4_t$5.log 2>&1
  echo "== $1 $3 seed=$4 trace=$5: rc=$? after $((SECONDS - T1)) s"
  grep -v "$F" $OUT/$1_$3_seed$4_t$5.log | grep "compared\|check:\|^{\|Error\|error" | cut -c1-2600 | tail -n 6
}
run parent .parent dots3-serve-longctx 3500060001 0
run change .proof dots3-serve-longctx 3500060102 1
head -n 8 $OUT/runs_change/dsa_steps.txt | cut -c1-200; grep -n "prefill_chunk:" -A 6 $OUT/runs_change/dsa_steps.txt | cut -c1-200
run parent .parent kimi-serve-backlog 3500060203 1
run parent .parent kimi-serve-backlog 3500060304 0
run change .proof kimi-serve-backlog 3500060304 0

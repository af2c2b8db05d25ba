set -u
ROOT=$PWD
mkdir -p $ROOT/chiprun_out/pr28/call3
run() { # side dir workload seed trace
  local out=$ROOT/chiprun_out/pr28/call3/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_KEEP_TRACE=$ROOT/chiprun_out/pr28/call3/trace_$1_$4 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(tail -n 1 $out | cut -c1-330)"
}
S=sdar-serve-backlog
run change .proof $S 2800010101 0
run parent .parent $S 2800010101 0
run parent .parent $S 2800010202 0
run change .proof $S 2800010202 0
run change .proof $S 2800010303 1
run change .proof $S 2800010404 0
run parent .parent $S 2800010404 0
run change .proof $S 2800010505 0
run change .proof $S 2800010606 0
run parent .parent gpt2s-serve-backlog 2800010707 0
run change .proof gpt2s-serve-backlog 2800010707 0

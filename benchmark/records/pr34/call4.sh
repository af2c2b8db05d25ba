# PR 34, call 4: the final tree as git would commit it (.proof = git archive of the index) against
# the parent (.parent = git archive of 252cf2d) in gpt2s-serve-chat: one traced run of the
# change, then three pairs (parent, change, change, parent, parent, change), a seed a pair
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr34/call4
mkdir -p $OUT
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_KEEP_TRACE=$OUT/trace_$1_$3_$4 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(tail -n 1 $out | cut -c1-600)"
}
B=gpt2s-serve-chat
run change .proof $B 3400040101 1
run parent .parent $B 3400040202 0
run change .proof $B 3400040202 0
run change .proof $B 3400040303 0
run parent .parent $B 3400040303 0
run parent .parent $B 3400040404 0
run change .proof $B 3400040404 0

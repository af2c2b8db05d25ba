# PR 31, call 3: the final tree as git would commit it (.proof = git archive of the index)
# against the parent (.parent = git archive of 806c89d): the two claimed cells traced and
# untraced, then one cell of each other configuration that imports a changed module
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr31/call3
mkdir -p $OUT
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_KEEP_TRACE=$OUT/trace_$1_$3_$4 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(tail -n 1 $out | cut -c1-400)"
}
B=gpt2s-serve-backlog
C=gpt2s-serve-chat
run change .proof $B 3100030101 1
run change .proof $B 3100030202 0
run parent .parent $B 3100030202 0
run change .proof $B 3100030303 0
run change .proof $C 3100030404 1
run parent .parent $C 3100030505 0
run change .proof $C 3100030505 0
run parent .parent sdar-serve-backlog 3100030606 0
run change .proof sdar-serve-backlog 3100030606 0
run change .proof gpt2s-train 3100030707 0
run parent .parent gpt2s-train 3100030707 0

# PR 34, call 2: the change (the working tree) in the two claimed cells, traced, and the parent
# (.parent = git archive of 252cf2d) beside it untraced: does the mechanism engage end to end
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr34/call2
mkdir -p $OUT
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_KEEP_TRACE=$OUT/trace_$1_$3_$4 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(tail -n 1 $out | cut -c1-600)"
}
B=gpt2s-serve-backlog
C=gpt2s-serve-chat
run change . $B 3400020101 1
run change . $B 3400020202 0
run parent .parent $B 3400020202 0
run change . $C 3400020303 1
run change . $C 3400020404 0
run parent .parent $C 3400020404 0

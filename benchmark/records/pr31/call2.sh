# PR 31, call 2: parent (.parent = git archive of 806c89d) against the change (the working
# tree) in the two claimed cells: parent, change, change, parent; a seed a pair
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr31/call2
mkdir -p $OUT
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_KEEP_TRACE=$OUT/trace_$1_$3_$4 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(tail -n 1 $out | cut -c1-400)"
}
B=gpt2s-serve-backlog
C=gpt2s-serve-chat
run parent .parent $B 3100020101 0
run change . $B 3100020101 0
run change . $B 3100020202 0
run parent .parent $B 3100020202 0
run parent .parent $C 3100020303 0
run change . $C 3100020303 0
run change . $C 3100020404 0
run parent .parent $C 3100020404 0

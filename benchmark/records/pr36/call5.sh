# PR 36, call 5: the other three served decoders share _dispatch_decode and _sample_emit and their
# programs equal the parent's (program_hashes.txt): one pair each, untraced, a seed a pair, the final
# tree (.proof) against the parent (.parent); expected unmoved (bound 4 %)
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr36/call5
mkdir -p $OUT
run() { # side dir workload seed
  local out=$OUT/$1_$3_seed$4_trace0.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace 0 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=0 rc=$? after $((SECONDS - T1)) s $(tail -n 1 $out | cut -c1-600)"
}
run parent .parent sdar-serve-backlog 3600050101
run change .proof sdar-serve-backlog 3600050101
run change .proof kimi-serve-backlog 3600050202
run parent .parent kimi-serve-backlog 3600050202
run parent .parent dots3-serve-longctx 3600050303
run change .proof dots3-serve-longctx 3600050303

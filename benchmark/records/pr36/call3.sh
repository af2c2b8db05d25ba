# PR 36, call 3: the final tree as git would commit it (.proof = git archive of the index) against
# the parent (.parent = git archive of 741199d) in gpt2s-serve-backlog, the claimed cell: one traced
# run of the change (the engine's /stats snapshots kept), then three pairs (parent, change, change,
# parent, parent, change), a seed a pair
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr36/${CALL:-call3}
mkdir -p $OUT
run() { # side dir workload seed trace [runner]
  local out=$OUT/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4 BENCHMARK_KEEP_TRACE=$OUT/trace_$1_$3_$4 python3 ${6:--m benchmark.run} --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(tail -n 1 $out | cut -c1-1500)"
}
B=${CELL:-gpt2s-serve-backlog}
S=${SEEDS:-36000301}
run change .proof $B ${S}01 1 benchmark/records/pr36/run_with_stats.py
run parent .parent $B ${S}02 0
run change .proof $B ${S}02 0
run change .proof $B ${S}03 0
run parent .parent $B ${S}03 0
run parent .parent $B ${S}04 0
run change .proof $B ${S}04 0
cat $OUT/change_${B}_${S}01/stats_snapshots.jsonl

# PR 36, call 2: the change (the working tree) in the two GPT-2 serving cells, traced (with the
# engine's /stats snapshots kept: run_with_stats.py), and the parent (.parent = git archive of
# 741199d) beside it untraced at the same seed: does the mechanism engage end to end
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr36/call2
mkdir -p $OUT
run() { # side dir workload seed trace [runner]
  local out=$OUT/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4 BENCHMARK_KEEP_TRACE=$OUT/trace_$1_$3_$4 python3 ${6:--m benchmark.run} --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(tail -n 1 $out | cut -c1-1500)"
}
B=gpt2s-serve-backlog
C=gpt2s-serve-chat
run change . $B 3600020101 1 benchmark/records/pr36/run_with_stats.py
run parent .parent $B 3600020202 0
run change . $B 3600020202 0
run change . $C 3600020303 1 benchmark/records/pr36/run_with_stats.py
run change . $C 3600020404 0
run parent .parent $C 3600020404 0
cat $OUT/change_${B}_3600020101/stats_snapshots.jsonl $OUT/change_${C}_3600020303/stats_snapshots.jsonl
du -sh $OUT

# PR 41, call 7 (after the driver's first refusal: `per_layer` had 138 entries of at most 128). The committed files
# alone (.proof = git archive of this PR's index) run the new cell traced and untraced on two new seeds: the thirteen
# quantities now read under dots3's entries have to be in the traced line. Then the parent (.parent = git archive of
# 16ff970 with this PR's benchmark files laid over it) fails the new cell at once and runs dots3-serve-longctx traced,
# the accepted cell whose entries' `workloads` this PR appended to.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr41/call7
mkdir -p $OUT
T0=$SECONDS
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_t$5.log T1=$SECONDS
  ( cd $2 && BENCH_RUN=call7 BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_t$5 timeout 900 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "== $1 $3 seed=$4 trace=$5 rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], {k: round(v['value'],4) for k,v in m.items()}, line['device'].get('memory_peak_bytes'), line['device'].get('busy_s'), line['device'].get('window_s'))" 2>&1 | tail -n 1)"
}
run change .proof laguna-serve-mixed 4100070101 1
grep "^compared\|launch_pairs" $OUT/change_laguna-serve-mixed_seed4100070101_t1.log | cut -c1-300
run change .proof laguna-serve-mixed 2147483999 0
run parent .parent laguna-serve-mixed 2147483999 0
tail -n 3 $OUT/parent_laguna-serve-mixed_seed2147483999_t0.log.err | cut -c1-300
run parent .parent dots3-serve-longctx 4100070202 1
tail -n 3 $OUT/parent_dots3-serve-longctx_seed4100070202_t1.log.err | cut -c1-300
du -sh $OUT

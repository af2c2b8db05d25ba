# PR 46, call 5 (after call 4): the seed of call 4's widest pair again, twice a side, from the COMMITTED files
# (.proof = git archive of this PR's index; .parent = git archive of e00e71d).
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr46/call5
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
T0=$SECONDS
# Call 4's widest pair was seed 1300040404 (p50 259.31 -> 268.25 on equal engine phases a step and a prefill): the SAME
# seed again twice a side (the record directories carry a suffix, so the first reading is kept). If the readings of one
# side at one seed differ among themselves as the two sides did, the 9 ms were the run's and not the tree's.
run2() { # side dir seed tag
  local out=$OUT/$1_gpt2s-serve-chat_seed$3$4_t0.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_gpt2s-serve-chat_$3$4_t0 python3 $ROOT/benchmark/records/pr46/run_with_stats.py --workload gpt2s-serve-chat --seed $3 --seconds 45 --trace 0 ) > $out 2> $out.err
  echo "== $1 seed=$3 $4 rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $out | cut -c1-400)"
}
run2 change $ROOT/.proof 1300040404 b
run2 parent $ROOT/.parent 1300040404 b
run2 parent $ROOT/.parent 1300040404 c
run2 change $ROOT/.proof 1300040404 c
du -sh $OUT

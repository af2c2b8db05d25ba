# PR 44, call C: one pair (parent = d6b9143 with this PR's benchmark files laid over it, change = .proof) at equal seeds
# in each of the three state-decoder cells that share the code this PR touched, untraced; parent, change, change, parent
# order across the cells.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr44/callC
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
T0=$SECONDS
run() { # side dir workload seed
  local out=$OUT/$1_$3_seed$4.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace 0 ) > $out 2> $out.err
  echo "== $1 $3 seed=$4 rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], {k: round(v['value'],3) for k,v in m.items()})")"
}
run parent $ROOT/.parent kimi-serve-backlog 4400050101
run change $ROOT/.proof kimi-serve-backlog 4400050101
run change $ROOT/.proof laguna-serve-mixed 4400050202
run parent $ROOT/.parent laguna-serve-mixed 4400050202
run parent $ROOT/.parent dots3-serve-longctx 4400050303
run change $ROOT/.proof dots3-serve-longctx 4400050303
du -sh $OUT

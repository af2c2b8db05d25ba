# PR 44, call 3: the limits' other readings at the cell's own size: each planted fault (benchmark/planted_mla.py), a
# 10 s window each (the ramp, the drain and the check are the cell's; only `correct` and the gaps are read).
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr44/call3
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
export BENCHMARK_RECORD_DIR=$OUT/runs
T0=$SECONDS
n=0
for fault in ${FAULTS:-rope_left_off_k group_limit_dropped routed_scale_left_off yarn_scale_left_off absorbed_uses_stale_row}; do
n=$((n + 1))
seed=$((${BASE:-4400030000} + 101 * n))
T1=$SECONDS
python3 -m benchmark.planted_mla --fault $fault --workload axk1-serve-longctx --seed $seed --seconds 10 --trace 0 > $OUT/${fault}_${seed}.log 2> $OUT/${fault}_${seed}.err
echo "== $fault seed $seed: rc=$? after $((SECONDS - T1)) s (call at $((SECONDS - T0)) s)"
grep "^compared\|^read served\|^check:\|^{" $OUT/${fault}_${seed}.log | cut -c1-400 | tail -n 8
done
du -sh $OUT

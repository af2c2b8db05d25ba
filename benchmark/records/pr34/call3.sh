# PR 34, call 3: the final tree as git would commit it (.proof = git archive of the index) against
# the parent (.parent = git archive of 252cf2d) in gpt2s-serve-backlog: the final sweep, one traced run
# of the change, then three pairs (parent, change, change, parent, parent, change), a seed a pair
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr34/call3
mkdir -p $OUT
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_KEEP_TRACE=$OUT/trace_$1_$3_$4 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(tail -n 1 $out | cut -c1-600)"
}
( cd .proof && python3 experiments/flash_sweep.py paged $OUT/paged_sweep.jsonl $ROOT/.parent/decode_attention_parent.py ) > $OUT/sweep.log 2>&1
echo "sweep rc=$? rows=$(grep -c . $OUT/paged_sweep.jsonl)"
B=gpt2s-serve-backlog
run change .proof $B 3400030101 1
run parent .parent $B 3400030202 0
run change .proof $B 3400030202 0
run change .proof $B 3400030303 0
run parent .parent $B 3400030303 0
run parent .parent $B 3400030404 0
run change .proof $B 3400030404 0

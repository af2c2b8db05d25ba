# PR 31, call 1: the chip smoke, then the change in gpt2s-serve-backlog, traced and untraced
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr31/call1
mkdir -p $OUT
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_KEEP_TRACE=$OUT/trace_$1_$3_$4 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(tail -n 1 $out | cut -c1-400)"
}
python3 chip_smoke.py > $OUT/smoke.log 2> $OUT/smoke.err; echo "smoke rc=$? $(tail -n 1 $OUT/smoke.log | cut -c1-300)"
run change . gpt2s-serve-backlog 3100000101 1
run change . gpt2s-serve-backlog 3100000202 0

# PR 37, call 1: the two fixtures of benchmark/tests/test_launch_pairs.py recorded on the chip, then the
# change (the working tree) traced in gpt2s-serve-backlog with what the new reader reads kept
# (run_keep_pairs.py): does the order-pairing pair a capture at the cell's own size
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr37/call1
mkdir -p $OUT
python3 benchmark/tests/record_pair_fixture.py > $OUT/record_pair_fixture.log 2> $OUT/record_pair_fixture.err
echo "record_pair_fixture rc=$?"; grep -v "^I0000\|^WARNING\|^W0000" $OUT/record_pair_fixture.log | cut -c1-1800
tail -n 5 $OUT/record_pair_fixture.err | cut -c1-400
run() { # side dir workload seed trace [runner]
  local out=$OUT/$1_$3_seed$4_trace$5.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4 python3 ${6:--m benchmark.run} --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? after $((SECONDS - T1)) s $(tail -n 1 $out | cut -c1-3000)"
  grep -h "xplane_join:\|launch_pairs:" $out | cut -c1-1200
}
run change . gpt2s-serve-backlog 3700010101 1 benchmark/records/pr37/run_keep_pairs.py
du -sh $OUT

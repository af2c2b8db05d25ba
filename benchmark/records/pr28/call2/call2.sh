set -u
mkdir -p chiprun_out/pr28
run() { # side dir seed trace
  local out=$PWD/chiprun_out/pr28/call2_$1_seed$3_trace$4.log
  ( cd $2 && python3 -m benchmark.run --workload sdar-serve-backlog --seed $3 --seconds 45 --trace $4 ) > $out 2> $out.err
  echo "$1 seed=$3 trace=$4 rc=$? $(tail -n 1 $out | cut -c1-700)"
}
run parent .parent 2800000101 0
run change . 2800000101 0
run change . 2800000202 0
run parent .parent 2800000202 0
run change . 2800000303 1
run parent .parent 2800000303 1

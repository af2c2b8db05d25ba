# PR 37, call 10: a second untraced pair, the parent first, in kimi-serve-backlog (the state cells share _Phase, _launch and the
# admit children): the parent (.parent) and the committed tree (.proof) at one seed
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr37/call10
mkdir -p $OUT
run() { # side dir
  local out=$OUT/$1_kimi-serve-backlog_seed3700100202_trace0.log T1=$SECONDS
  ( cd $2 && python3 -m benchmark.run --workload kimi-serve-backlog --seed 3700100202 --seconds 45 --trace 0 ) > $out 2> $out.err
  echo "$1 kimi-serve-backlog seed=3700100202 trace=0 rc=$? after $((SECONDS - T1)) s $(tail -n 1 $out | cut -c1-400)"
}
run parent .parent
run change .proof

# PR 37, call 2: what a clock read costs on the chip's host (the first call read thread_time() in
# steps of 10 ms there); the two fixtures again, by the recorder as committed (operations' text cut);
# then the change (the working tree) traced in the three cells whose programs hold no while:
# kimi-serve-backlog, dots3-serve-longctx, sdar-serve-backlog (run_keep_pairs.py keeps the pairs
# and the phase counters at the window's edges)
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr37/call2
mkdir -p $OUT
env | grep -i "JAX_COMP\|^HOME\|TMPDIR" ; uname -a
python3 - <<'PY' | tee $OUT/clocks.txt
import time
print({k: (time.get_clock_info(k).resolution, time.get_clock_info(k).implementation)
       for k in ("thread_time", "perf_counter", "process_time")})
for name in ("thread_time", "perf_counter", "process_time", "monotonic"):
    f = getattr(time, name); n = 200000
    t = time.perf_counter()
    for _ in range(n): f()
    print(name, "us a call", 1e6 * (time.perf_counter() - t) / n)
seen = set()
t = time.perf_counter()
while time.perf_counter() - t < 0.5:
    seen.add(time.thread_time())
print("distinct thread_time readings in 0.5 s of spinning", len(seen), sorted(seen)[:5])
PY
python3 benchmark/tests/record_pair_fixture.py > $OUT/record_pair_fixture.log 2> $OUT/record_pair_fixture.err
echo "record_pair_fixture rc=$?"; grep -v "^I0000\|^WARNING\|^W0000\|^xplane_join" $OUT/record_pair_fixture.log | cut -c1-1500
tail -n 3 $OUT/record_pair_fixture.err | cut -c1-300
run() { # side dir workload seed trace [runner]
  local out=$OUT/$1_$3_seed$4_trace$5.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4 python3 ${6:--m benchmark.run} --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? after $((SECONDS - T1)) s $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], {k: round(v['value'],4) for k,v in m.items() if k.startswith(('sched_','serve_device_idle','serve_completed'))})")"
  grep -h "xplane_join:\|launch_pairs:" $out | cut -c1-900
  cat $OUT/$1_$3_$4/phase_seconds.jsonl
}
K=benchmark/records/pr37/run_keep_pairs.py
run change . kimi-serve-backlog 3700020101 1 $K
run change . dots3-serve-longctx 3700020202 1 $K
run change . sdar-serve-backlog 3700020303 1 $K
du -sh $OUT

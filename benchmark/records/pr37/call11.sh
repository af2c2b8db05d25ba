# PR 37, call 11: the driver's traced run of the PARENT with this PR's benchmark files laid over it
# (.parent = git archive of b8cb645 + BENCHMARK.json and benchmark/ of the tree): the fourteen new
# metrics must read nothing there and raise nothing. sdar-serve-backlog: the shortest serving cell
set -u
OUT=$PWD/chiprun_out/pr37/call11
mkdir -p $OUT
( cd .parent && python3 -m benchmark.run --workload sdar-serve-backlog --seed 3700110101 --seconds 45 --trace 1 ) > $OUT/parent_overlay_sdar_trace1.log 2> $OUT/parent_overlay_sdar_trace1.log.err
echo "rc=$?"; tail -n 1 $OUT/parent_overlay_sdar_trace1.log | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], len(m), sorted(k for k in m if 'admit_' in k or 'pair' in k or 'offcpu' in k))"
grep -h "launch_pairs:\|xplane_join:" $OUT/parent_overlay_sdar_trace1.log | cut -c1-300

# PR 44, call B: the committed files alone (.proof = git archive of this PR's index): three planted faults first
# (benchmark/planted_mla.py; a 10 s window each: the ramp, the drain and the check are the cell's), then set B of six
# untraced sound runs on six unlike seeds. (The other two faults: `BASE=4400031000 FAULTS="yarn_scale_left_off
# absorbed_uses_stale_row" bash benchmark/records/pr44/call3.sh`: not run in PR 44's session.)
set -u
ROOT=$PWD
( cd $ROOT/.proof && FAULTS="rope_left_off_k group_limit_dropped routed_scale_left_off" bash benchmark/records/pr44/call3.sh )
mkdir -p $ROOT/chiprun_out/pr44 && cp -r $ROOT/.proof/chiprun_out/pr44/call3 $ROOT/chiprun_out/pr44/call3_first
export SRC=$ROOT/.proof SET=b CONTROL=0
export SEEDS="${SEEDS:-4400040101 1500040202 2147470303 800040404 3700040505 77040606}"
bash benchmark/records/pr44/call2.sh

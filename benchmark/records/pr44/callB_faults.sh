# PR 44: what the end of the session leaves room for: the three planted faults alone, from the committed files.
set -u
ROOT=$PWD
( cd $ROOT/.proof && FAULTS="rope_left_off_k group_limit_dropped routed_scale_left_off" bash benchmark/records/pr44/call3.sh )
mkdir -p $ROOT/chiprun_out/pr44 && cp -r $ROOT/.proof/chiprun_out/pr44/call3 $ROOT/chiprun_out/pr44/call3_first

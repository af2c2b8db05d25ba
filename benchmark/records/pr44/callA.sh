# PR 44, call A (one machine held as long as it is useful: the chip was scarce for hours): call 1 (the pieces, the
# parent on the new cell, one traced sound run) and, if that run printed a correct line, call 2 (set A of six untraced
# runs, then the fp8 control cold).
set -u
bash benchmark/records/pr44/call1.sh
last=$(ls chiprun_out/pr44/call1/change_axk1-serve-longctx_seed*_t1.log | head -n 1)
if tail -n 1 "$last" | grep -q '"correct": true'; then
  bash benchmark/records/pr44/call2.sh
else
  echo "== the traced run printed no correct line: set A not run"; tail -n 30 "$last.err" | cut -c1-300
fi

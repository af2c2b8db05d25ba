# PR 46: every exported program of the six served configurations, parent (e00e71d, git archive) against change (git
# archive of this PR's index), each tree copied in turn to ONE path (/root/scratch/tree_x). On the CPU; nothing runs.
#   bash benchmark/records/pr46/program_hashes.sh > benchmark/records/pr46/program_hashes.body.txt
set -eu
REPO=$PWD
X=/root/scratch/tree_x
export JAX_TRACEBACK_IN_LOCATIONS_LIMIT=1 JAX_PLATFORMS=cpu
for side in parent change; do
  rm -rf $X && mkdir -p $X
  if [ $side = parent ]; then git -C $REPO archive e00e71dec4728c8cf20fd7caf174e0e04634693b | tar -x -C $X
  else git -C $REPO archive $(git -C $REPO write-tree) | tar -x -C $X; fi
  echo "== part 1 (cells' shapes), $side"
  python3 $REPO/benchmark/records/pr38/program_hashes.py $X 2>/dev/null | grep -v "^/root"
  echo "== part 2 (tiny exports), $side"
  python3 $REPO/benchmark/records/pr44/program_hashes.py $X 2>/dev/null | grep -v "^/root"
  echo "== part 3 (tiny SDAR export, three widths), $side"
  python3 $REPO/benchmark/records/pr46/program_hashes.py $X 2>/dev/null | grep -v "^/root"
done
rm -rf $X

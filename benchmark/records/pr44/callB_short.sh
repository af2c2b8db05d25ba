# PR 44: call B cut to what the end of the session leaves room for: the three faults, then three seeds of set B.
export SEEDS="4400040101 1500040202 2147470303"
bash benchmark/records/pr44/callB.sh

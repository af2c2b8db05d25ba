# PR 36, call 1: the fetch loop at the GPT-2 serving cells' shape, before the engine is touched
set -x
mkdir -p chiprun_out/pr36/call1
python3 benchmark/records/pr36/fetch_loop.py chiprun_out/pr36/call1/fetch_loop.json
echo "rc=$?"

# PR 37, call 7: do short bursts after a sleep count on the chip host's CPU clock (clock_bursts.py),
# then what the instrumentation costs in gpt2s-serve-backlog (call_cost.sh, as calls 4 and 6 for the
# chat cell): three pairs untraced, and the change again with --trace 1
mkdir -p chiprun_out/pr37/call7
python3 benchmark/records/pr37/clock_bursts.py | tee chiprun_out/pr37/call7/clock_bursts.jsonl
bash benchmark/records/pr37/call_cost.sh gpt2s-serve-backlog 7

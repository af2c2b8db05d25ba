# PR 36, call 4: call 3's seven runs in gpt2s-serve-chat (expected to fall, not claimed)
CALL=call4 CELL=gpt2s-serve-chat SEEDS=36000401 bash benchmark/records/pr36/call3.sh

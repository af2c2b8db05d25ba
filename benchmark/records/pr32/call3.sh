mkdir -p chiprun_out/pr32/call3
python3 chiprun_out_check.py > chiprun_out/pr32/call3/checks.log 2>&1; echo rc=$?; grep -v Warning chiprun_out/pr32/call3/checks.log | tail -12

set -x
mkdir -p chiprun_out/pr34/call2
python3 experiments/flash_sweep.py paged chiprun_out/pr34/call2/pairs_sweep.jsonl > chiprun_out/pr34/call2/sweep.log 2>&1
echo "sweep rc=$?"
python3 - <<'PY'
import json
for l in open("chiprun_out/pr34/call2/pairs_sweep.jsonl"):
    r = json.loads(l)
    print(r.get("rows"), f'{r.get("heads")}x{r.get("head_dim")}', r.get("ctx"), r.get("heads_by"), "ms", r.get("ms"), "roof%", r.get("roofline_pct"), "diff", r.get("max_abs_diff"), r.get("error", "")[:150])
PY
set +x
bash benchmark/records/pr34/call2.sh

set -x
mkdir -p chiprun_out/pr34/call1
python3 experiments/flash_sweep.py paged chiprun_out/pr34/call1/paged_sweep.jsonl .parent/decode_attention_parent.py > chiprun_out/pr34/call1/sweep.log 2>&1
echo "sweep rc=$?"
grep -c . chiprun_out/pr34/call1/paged_sweep.jsonl
python3 - <<'PY'
import json
for l in open("chiprun_out/pr34/call1/paged_sweep.jsonl"):
    r = json.loads(l)
    print(r.get("kernel"), r.get("rows"), f'{r.get("heads")}x{r.get("head_dim")}', r.get("ctx"), r.get("dtype"), r.get("impl"), r.get("fetch", ""), "e", r.get("entries"), "steps", r.get("grid_steps"), "live", r.get("live_blocks"), "ms", r.get("ms"), "other", r.get("other_ms"), "roof%", r.get("roofline_pct"), "diff", r.get("max_abs_diff"), r.get("error", "")[:150])
PY

# PR 34, call 5: (a) what export.json and /stats say of gpt2-small's served programs, exported on the
# chip as the serving cells export them (64 slots, blocks of 128, 512 + 256 tokens; + a verify
# program at K = 4); (b) three more traced runs of the change (.proof), two of them in
# gpt2s-serve-chat: call 4's capture had its device clock 4.3 ms late and paired spans wrongly
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr34/call5
mkdir -p $OUT
( cd .proof && python3 - > $OUT/attn_schedule.json 2> $OUT/attn_schedule.err <<'PY'
import json, os, sys, tempfile
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from distributed_tensorflow_example_tpu.models.gpt import GPT, GPTConfig
from distributed_tensorflow_example_tpu.serving import export_generator, load_stepwise
from distributed_tensorflow_example_tpu.serving_batch import GenerationEngine
cfg = GPTConfig.small(); cfg.vocab_size, cfg.dropout = 50257, 0.0
model = GPT(cfg, dtype=jnp.bfloat16, attention_impl="flash")
params = model.init(jax.random.key(0))
d = tempfile.mkdtemp()
export_generator(model, params, d, ragged=True, stepwise=True, paged=True, slots=64, block_size=128,
                 prompt_len=512, max_new_tokens=256, spec_tokens=4, platforms=("tpu",))
meta = json.load(open(os.path.join(d, "export.json")))
eng = GenerationEngine(load_stepwise(d), prefix_cache=False)
print(json.dumps({"device": jax.devices()[0].device_kind,
                  "export.json stepwise.decode.attn_schedule": meta["stepwise"]["decode"]["attn_schedule"],
                  "/stats attn_schedule": eng.stats()["attn_schedule"]}, indent=1))
PY
)
echo "attn_schedule rc=$?"; cat $OUT/attn_schedule.json; tail -3 $OUT/attn_schedule.err
run() { # side dir workload seed trace
  local out=$OUT/$1_$3_seed$4_trace$5.log
  ( cd $2 && BENCHMARK_KEEP_TRACE=$OUT/trace_$1_$3_$4 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace $5 ) > $out 2> $out.err
  echo "$1 $3 seed=$4 trace=$5 rc=$? $(grep xplane_join $out | cut -c1-300)"
  echo "   $(tail -n 1 $out | cut -c1-400)"
}
run change .proof gpt2s-serve-chat 3400050101 1
run change .proof gpt2s-serve-chat 3400050202 1
run change .proof gpt2s-serve-backlog 3400050303 1

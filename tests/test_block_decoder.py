"""The block-diffusion decoder (``models/decoder.py``: SDAR-30B-A3B-Chat's
block at test widths) against its plain reference
(``benchmark/reference/sdar-30b-a3b-chat.py``), seeded random weights:
the forwards, the expert layer that holds a share, the kernels, the
artifact with weights as arguments, and the engine's block steps."""

import importlib
import json
import os
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights_by_leaf                      # noqa: E402
from benchmark.manifest import load_module                 # noqa: E402
from distributed_tensorflow_example_tpu import serving     # noqa: E402
from distributed_tensorflow_example_tpu.config import TrainConfig  # noqa: E402
from distributed_tensorflow_example_tpu.models import get_model   # noqa: E402
from distributed_tensorflow_example_tpu.models.decoder import (  # noqa: E402
    BlockDecoder, DecoderBlockConfig)
from distributed_tensorflow_example_tpu.ops import moe as moe_mod   # noqa: E402
from distributed_tensorflow_example_tpu.ops.moe import (  # noqa: E402
    moe_dropless, pair_bound, ragged_tiling)
from distributed_tensorflow_example_tpu.serving_batch import (  # noqa: E402
    GenerationEngine)

# the modules, not the same-named functions ops.pallas re-exports
decode_mod = importlib.import_module(
    "distributed_tensorflow_example_tpu.ops.pallas.decode_attention")
flash_mod = importlib.import_module(
    "distributed_tensorflow_example_tpu.ops.pallas.flash_attention")
ref = load_module(os.path.join(ROOT, "benchmark", "reference",
                               "sdar-30b-a3b-chat.py"))

CFG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs",
    "sdar-30b-a3b-chat.json")))["rehearsal"]["sizes"]
LANES, MASK = 4, 511


def build(dtype: str, seed: int = 7):
    model = get_model("sdar_moe_tiny", TrainConfig(
        model="sdar_moe_tiny", dtype=dtype, param_dtype=dtype))
    model.cfg.denoising_steps = 2
    params = weights_by_leaf.make_params(ref.param_spec(CFG), seed, dtype)
    return model, params


@pytest.fixture(scope="module")
def f32():
    return build("float32")


def test_registry_builds_the_block_from_a_description():
    big = get_model("sdar_moe", TrainConfig(model="sdar_moe", num_layers=6))
    c = big.cfg
    assert (c.hidden, c.heads, c.kv_heads, c.head_dim, c.experts,
            c.experts_per_token, c.expert_width, c.vocab_size, c.layers,
            c.rope_theta) == (2048, 32, 4, 128, 128, 8, 768, 151936, 6, 1e6)
    from distributed_tensorflow_example_tpu.models.decoder import (
        BlockDecoder, DecoderBlockConfig)
    for bad in (dict(block_length=3), dict(heads=6, kv_heads=4),
                dict(first_expert=4, experts_held=8, experts=8)):
        with pytest.raises(ValueError):
            BlockDecoder(DecoderBlockConfig(**{**DecoderBlockConfig.tiny(
            ).__dict__, **bad}))
    with pytest.raises(NotImplementedError, match="served decoder"):
        big.loss(None, None, None, None)
    # the program's tree is the reference's, leaf for leaf
    tiny, params = build("bfloat16")
    shapes = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)),
                                    jax.eval_shape(tiny.init,
                                                   jax.random.key(0)))
    assert shapes == jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), params)


# ---- (a) the forwards against the reference ---------------------------

def _prefill_and_step(model, params, prompt, dtype, impl="xla"):
    """Paged prefill of ``prompt`` then the first block step, through the
    pool: (ids, conf) of the block's lanes and the block's tokens."""
    p = len(prompt)
    s0, bs, n = 32, 16, 9
    layers, width = model.cfg.layers, model.cfg.kv_heads * model.cfg.head_dim
    ids = np.zeros((1, s0), np.int32)
    ids[0, :p] = prompt
    pool = jnp.zeros((layers, n, bs, width), dtype)
    kp, vp = model.paged_prefill(params, ids, np.ones((1, s0), np.int32),
                                 pool, pool, jnp.array([3, 5], jnp.int32),
                                 attention="xla")
    start = p - p % LANES
    blk = list(prompt[start:]) + [MASK] * (LANES - p % LANES)
    bt = np.zeros((2, 3), np.int32)
    bt[1] = [3, 5, 7]
    tok = np.zeros((2, LANES), np.int32)
    tok[1] = blk
    out = model.block_step(params, kp, vp, bt, tok,
                           np.array([0, start], np.int32),
                           np.array([0, 1]), np.array([0, 0]),
                           attention=impl)
    return out, start, blk


@pytest.mark.parametrize("p", [22, 20, 3, 32])
def test_prefill_then_block_step_match_the_reference_f32(f32, p):
    """float32 program against the float32 reference: the same function,
    so ids agree and confidences agree to float32 rounding (1e-5
    relative: a softmax over 512 logits after two layers)."""
    model, params = f32
    prompt = np.random.RandomState(p).randint(0, 500, p)
    out, start, blk = _prefill_and_step(model, params, prompt, jnp.float32)
    lg = ref.block_step(CFG, params, jnp.asarray(prompt[:start]),
                        jnp.asarray(blk, jnp.int32))
    conf = jax.nn.softmax(lg, -1).max(-1)
    np.testing.assert_array_equal(out["ids"][1], np.argmax(lg, -1))
    np.testing.assert_allclose(out["conf"][1], conf, rtol=1e-5)
    assert not np.asarray(out["conf"][0]).any()     # the dead row
    assert int(out["expert_rows"]) <= 2 * 8
    # and the reference's padded form is its plain form
    x = np.zeros((40,), np.int32)
    x[:start] = prompt[:start]
    x[start:start + LANES] = blk
    x[start + LANES:] = 77
    np.testing.assert_allclose(
        ref.block_logits_at(CFG, params, jnp.asarray(x), start, LANES), lg,
        rtol=1e-5, atol=1e-6)


def test_block_step_bf16_stays_near_the_reference():
    """bfloat16 storage and operands against the float32 reference on the
    same (bfloat16) weights: logit-level agreement to bfloat16's 2^-8 per
    operand through two layers: confidences within 2 % (relative), and
    the committed id within 0.02 of the reference's best logit (logits
    spread ~0.5 here). A seed whose routing has no near-tie: one expert
    chosen otherwise moves these by ten times as much (the configuration
    file's ``why_float32``)."""
    model, params = build("bfloat16", seed=11)
    prompt = np.random.RandomState(5).randint(0, 500, 22)
    out, start, blk = _prefill_and_step(model, params, prompt, jnp.bfloat16)
    lg = np.asarray(ref.block_step(CFG, params, jnp.asarray(prompt[:start]),
                                   jnp.asarray(blk, jnp.int32)))
    conf = np.asarray(jax.nn.softmax(lg, -1).max(-1))
    np.testing.assert_allclose(out["conf"][1], conf, rtol=0.02)
    got = np.asarray(out["ids"][1])
    assert (lg.max(-1) - lg[np.arange(LANES), got]).max() < 0.02


def simulate(params, prompt, max_new, steps=2, threshold=0.9):
    """Generation by diffusion over blocks with the REFERENCE's block
    step and no cache: tokens, per token its unmask step, forwards."""
    fwd = jax.jit(lambda pre, blk: ref.block_step(CFG, params, pre, blk))
    n_s = -(-LANES // steps)
    seq = list(prompt)
    start = len(seq) - len(seq) % LANES
    out, when, forwards = [], [], 0
    while len(out) < max_new:
        known = seq[start:]
        blk = known + [MASK] * (LANES - len(known))
        um = [0] * len(known) + [-1] * (LANES - len(known))
        step = 0
        while -1 in um:
            lg = np.asarray(fwd(jnp.asarray(seq[:start], jnp.int32),
                                jnp.asarray(blk, jnp.int32)))
            forwards += 1
            step += 1
            conf = np.asarray(jax.nn.softmax(lg, -1).max(-1))
            masked = [j for j in range(LANES) if um[j] < 0]
            chosen = [j for j in masked if conf[j] > threshold]
            if len(chosen) < n_s:
                chosen = sorted(masked, key=lambda j: (-conf[j], j))[:n_s]
            for j in chosen:
                blk[j], um[j] = int(lg[j].argmax()), step
        forwards += 1                       # the commit forward
        for j in range(LANES):
            if um[j]:
                out.append(blk[j])
                when.append(um[j])
        seq = seq[:start] + blk
        start += LANES
    return out[:max_new], when[:max_new], forwards


@pytest.fixture(scope="module")
def artifact(f32, tmp_path_factory):
    model, params = f32
    d = str(tmp_path_factory.mktemp("sdar"))
    serving.export_generator(model, params, d, ragged=True, stepwise=True,
                             paged=True, slots=3, block_size=16,
                             prompt_len=32, max_new_tokens=16,
                             platforms=("cpu",))
    return d


@pytest.fixture(scope="module")
def engine(artifact):
    eng = GenerationEngine(serving.load_stepwise(artifact),
                           max_queue=32).start()
    yield eng
    eng.close()


@pytest.mark.parametrize("p,max_new", [(22, 7), (20, 16), (3, 5), (32, 9),
                                       (1, 1), (31, 16)])
def test_engine_generates_what_the_reference_generates(f32, engine, p,
                                                       max_new):
    """Prefill + block steps through the engine (float32, so exact):
    prompts ending on and inside a block, ``max_new`` ending inside one
    (the surplus dropped). Tokens, per-token unmask steps and the
    forwards taken equal the reference's cacheless generation."""
    _, params = f32
    prompt = np.random.RandomState(100 + p).randint(0, 500, p).tolist()
    h = engine.submit(prompt, max_new=max_new)
    got = h.result(120)
    want, when, forwards = simulate(params, prompt, max_new)
    assert got == want
    assert h.timings["unmask_step"] == when
    assert h.timings["forwards"] == forwards
    assert h.timings["tokens"] == max_new


def test_engine_batches_denoising_and_commit_rows_in_one_dispatch(
        f32, engine):
    """More requests than slots, ragged lengths: every request still
    equals the reference, slots ride one dispatch a step (fewer block
    steps than forwards), counters count tokens and forwards."""
    _, params = f32
    rs = np.random.RandomState(3)
    before = engine.stats()
    hs = [engine.submit(rs.randint(0, 500, int(rs.randint(1, 33))).tolist(),
                        max_new=int(rs.randint(1, 17))) for _ in range(8)]
    for h in hs:
        assert h.result(120) == simulate(params, h.req.prompt.tolist(),
                                         h.req.max_new)[0]
    after = engine.stats()
    d = {k: after[k] - before[k] for k in (
        "block_steps", "denoise_forwards", "commit_forwards",
        "tokens_committed", "moe_rows", "decode_steps", "requests_done")}
    forwards = sum(h.timings["forwards"] for h in hs)
    assert d["denoise_forwards"] + d["commit_forwards"] == forwards
    assert d["block_steps"] == d["decode_steps"] < forwards
    assert d["tokens_committed"] >= sum(h.req.max_new for h in hs)
    assert d["moe_rows"] == forwards * LANES * 2 * 2   # lanes x layers x k
    assert after["moe_max_expert_load_ratio"] > 0
    assert after["blocks_free"] == after["blocks_total"]
    assert after["prefix_cache_entries"] == 0 and after["cow_copies"] == 0


def test_what_is_refused_for_this_artifact(f32, artifact, tmp_path):
    model, params = f32
    kw = dict(ragged=True, stepwise=True, paged=True, slots=2,
              block_size=16, prompt_len=32, max_new_tokens=16,
              platforms=("cpu",))
    for bad in (dict(spec_tokens=4), dict(weight_quant="int8"),
                dict(kv_cache_dtype="int8"), dict(prefill_chunk=16),
                dict(paged=False), dict(block_size=6)):
        with pytest.raises(ValueError):
            serving.export_generator(model, params, str(tmp_path / "x"),
                                     **{**kw, **bad})
    sw = serving.load_stepwise(artifact)
    for bad in (dict(spec_tokens=4), dict(prefill_chunk_tokens=16)):
        with pytest.raises(ValueError, match="diffusion over blocks"):
            GenerationEngine(sw, **bad)
    eng = GenerationEngine(sw)              # prefix cache: off, not refused
    assert eng.cache.prefix is None and eng.block["length"] == LANES
    with pytest.raises(ValueError, match="greedily"):
        eng.submit([1, 2, 3], temperature=0.7)
    eng.close()
    with pytest.raises(ValueError, match="scheduler on"):
        serving.load_servable(artifact)({"input_ids": np.zeros((1, 32))})


# ---- (a2) the prefill's widths: export, load, admission ------------------

W_NARROW, W_WIDE = 16, 32         # the artifact's widths (block_size 16)


@pytest.mark.parametrize("prompt_len,block_size,want", [
    (4096, 128, [4096, 2048, 1024]),     # the served cell
    (32, 16, [32, 16]),                  # the rehearsal: 8 is no whole block
    (48, 16, [48]),                      # 24 and 12 are none either
    (16, 16, [16])])
def test_prefill_widths_is_a_rule_on_shapes(prompt_len, block_size, want):
    assert serving.prefill_widths(prompt_len, block_size) == want


def _width_for(p):
    return min(w for w in (W_NARROW, W_WIDE) if w >= p)


@pytest.fixture(scope="module")
def wide_engine(artifact):
    """An engine held to the widest program, as the parent admits: the
    test narrows what the engine sees of the artifact, not the program."""
    eng = GenerationEngine(serving.load_stepwise(artifact), max_queue=32)
    eng.prefill_widths = (W_WIDE,)
    eng.start()
    yield eng
    eng.close()


@pytest.mark.parametrize("p", [1, W_NARROW - 1, W_NARROW, W_NARROW + 1,
                               W_WIDE])
def test_narrowest_width_leaves_the_pool_and_the_tokens_of_the_widest(
        artifact, engine, wide_engine, p):
    """What a prompt leaves in its pool blocks through the narrowest
    program that holds it is what it leaves through ``prompt_len`` (XLA
    attention, float32: to rounding, the reductions run over other
    lengths), nothing but the null block is written beside them, and an
    engine run generates the same tokens through either."""
    sw = serving.load_stepwise(artifact)
    prompt = np.random.RandomState(40 + p).randint(0, 500, p)
    run = [3, 5][:-(-p // 16)]

    def leaves(width):
        ids = np.zeros((1, width), np.int32)
        ids[0, :p] = prompt
        row = np.zeros((width // 16,), np.int32)
        row[:len(run)] = run
        out = sw.prefill({"input_ids": ids,
                          "prompt_mask": np.ones_like(ids),
                          "table_row": row, **sw.make_pool()})
        return np.asarray(out["cache_k"]), np.asarray(out["cache_v"])

    narrow, wide = leaves(_width_for(p)), leaves(W_WIDE)
    for a, b in zip(narrow, wide):
        assert np.abs(b[:, run]).max() > 0
        np.testing.assert_allclose(a[:, run], b[:, run], rtol=1e-5,
                                   atol=1e-6)
        rest = [i for i in range(1, a.shape[1]) if i not in run]
        assert not a[:, rest].any() and not b[:, rest].any()
    before = engine.stats()["prefills_by_width"]
    got = engine.generate(prompt.tolist(), max_new=9)
    after = engine.stats()["prefills_by_width"]
    assert got == wide_engine.generate(prompt.tolist(), max_new=9)
    assert {w: after[w] - before[w] for w in after} == {
        w: int(w == _width_for(p)) for w in (W_NARROW, W_WIDE)}


def test_engine_admits_through_the_narrowest_width_and_stats_say_so(
        artifact):
    """``/stats`` and the ``prefill`` span name the width each prompt
    took; the pad share is the rows no prompt token filled."""
    from distributed_tensorflow_example_tpu.obs.trace import (
        TraceRecorder, recorder, set_recorder)
    old = recorder()
    rec = set_recorder(TraceRecorder(max_events=1 << 14))
    rec.start()
    eng = GenerationEngine(serving.load_stepwise(artifact)).start()
    try:
        assert eng.prefill_widths == (W_NARROW, W_WIDE)
        lens = [5, 16, 17, 32, 1]
        for n in lens:
            eng.generate(list(range(1, n + 1)), max_new=5)
        st = eng.stats()
    finally:
        eng.close()
        rec.stop()
        set_recorder(old)
    assert st["prefills_by_width"] == {W_NARROW: 3, W_WIDE: 2}
    assert st["prefills"] == 5
    rows = 3 * W_NARROW + 2 * W_WIDE
    assert st["prefill_pad_share"] == round(1 - sum(lens) / rows, 4)
    snap = eng.metrics_snapshot()
    assert snap["serving_prefill_rows_total"]["value"] == rows
    assert snap["serving_prefill_tokens_total"]["value"] == sum(lens)
    spans = [it[5] for it in rec.drain() if it[2] == "prefill"]
    # the two launches at load (no prompt) come first
    assert [(a["prompt_tokens"], a["width"]) for a in spans] == [
        (0, W_NARROW), (0, W_WIDE), *((n, _width_for(n)) for n in lens)]


def test_every_width_is_compiled_before_the_engine_is_up(artifact):
    """Both widths are compiled when the constructor returns (counted as
    the engine's compilations), and a run that uses every width after the
    first request compiles nothing: no jit cache of the artifact's
    programs misses again."""
    sw = serving.load_stepwise(artifact)
    assert sorted(sw._prefills) == [W_NARROW, W_WIDE]
    assert all(f._cache_size() == 0 for f in sw._prefills.values())
    eng = GenerationEngine(sw)
    try:
        assert all(f._cache_size() == 1 for f in sw._prefills.values())
        assert eng.stats()["jit_compiles"] >= 2
        eng.start()
        eng.generate(list(range(1, 31)), max_new=8)    # the warm request
        compiled = eng.stats()["jit_compiles"]
        for n in (1, 15, 16, 17, 32, 9, 24):
            eng.generate(list(range(1, n + 1)), max_new=8)
        st = eng.stats()
        assert st["jit_compiles"] == compiled
        assert all(n > 0 for n in st["prefills_by_width"].values())
        assert all(f._cache_size() == 1 for f in sw._prefills.values())
        assert sw._decode._cache_size() == 1
    finally:
        eng.close()


def test_artifact_without_the_widths_key_loads_with_one_width(artifact,
                                                              tmp_path):
    """An artifact exported before the widths were (no
    ``stepwise.prefill_widths``) has one, ``prompt_len``: nothing is
    compiled at load, every prompt takes it, the tokens are the same."""
    import shutil
    d = str(tmp_path / "old")
    shutil.copytree(artifact, d)
    path = os.path.join(d, "export.json")
    meta = json.load(open(path))
    assert meta["stepwise"].pop("prefill_widths") == [W_WIDE, W_NARROW]
    os.remove(os.path.join(d, f"prefill_{W_NARROW}.stablehlo"))
    with open(path, "w") as f:
        json.dump(meta, f)
    sw = serving.load_stepwise(d)
    assert sw.prefill_widths == (W_WIDE,)
    eng = GenerationEngine(sw)
    assert sw._prefill._cache_size() == 0          # compiled at first use
    eng.start()
    new = GenerationEngine(serving.load_stepwise(artifact)).start()
    try:
        for n in (3, 16, 32):
            prompt = np.random.RandomState(n).randint(0, 500, n).tolist()
            assert eng.generate(prompt, max_new=7) == new.generate(
                prompt, max_new=7)
        assert eng.stats()["prefills_by_width"] == {W_WIDE: 3}
    finally:
        eng.close()
        new.close()


def test_block_artifact_holds_one_program_a_width(artifact):
    meta = json.load(open(os.path.join(artifact, "export.json")))
    assert meta["stepwise"]["prefill_widths"] == [W_WIDE, W_NARROW]
    assert sorted(f for f in os.listdir(artifact)
                  if f.endswith(".stablehlo")) == [
        "block_step.stablehlo", "prefill.stablehlo",
        f"prefill_{W_NARROW}.stablehlo"]


@pytest.mark.parametrize("paged", [True, False])
def test_gpt_artifacts_keep_one_prefill_program(tmp_path, paged):
    """GPT-2's exporters are not touched: one ``prefill*.stablehlo``, no
    widths key, and the engine admits every prompt through it."""
    gpt = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    d = str(tmp_path / "gpt")
    serving.export_generator(gpt, gpt.init(jax.random.key(0)), d,
                             ragged=True, stepwise=True, paged=paged,
                             slots=2, block_size=16, prompt_len=32,
                             max_new_tokens=8, platforms=("cpu",))
    assert [f for f in sorted(os.listdir(d)) if f.startswith("prefill")] == [
        "prefill.stablehlo"]
    meta = json.load(open(os.path.join(d, "export.json")))
    assert "prefill_widths" not in meta["stepwise"]
    sw = serving.load_stepwise(d)
    assert sw.prefill_widths == (32,)
    eng = GenerationEngine(sw, prefix_cache=False)
    assert sw._prefill._cache_size() == 0          # compiled at first use
    eng.start()
    try:
        for n in (3, 16, 17):
            assert len(eng.generate(list(range(1, n + 1)), max_new=4)) == 4
        assert eng.stats()["prefills_by_width"] == {
            32: 3 if paged else 0}
        assert sw._prefill._cache_size() == 1
    finally:
        eng.close()


# ---- (b) the expert layer that holds a share --------------------------

def _moe_case(skew: float):
    k = jax.random.split(jax.random.key(1), 5)
    t, h, e, f = 40, 64, 8, 32
    x = jax.random.normal(k[0], (t, h))
    router = jax.random.normal(k[1], (h, e)) * 0.2
    # skewed: a feature every row has sends expert 0 nearly all of them
    x = x.at[:, 0].set(1.0)
    router = router.at[0, 0].add(8.0 * skew)
    experts = {n: jax.random.normal(kk, s) * 0.2 for n, kk, s in (
        ("gate", k[2], (e, h, f)), ("up", k[3], (e, h, f)),
        ("down", k[4], (e, f, h)))}
    cfg = dict(CFG, num_experts=e, num_experts_per_tok=2)
    return x, router, experts, cfg


@pytest.mark.parametrize("skew", [0.0, 0.5])
def test_dropless_layer_equals_the_dense_loop(skew):
    """No sort and no capacity in the reference; the sorted grouped
    matmul must give the same layer, also when the router sends most
    rows to one expert (nothing is dropped). float32 both: 1e-5."""
    x, router, experts, cfg = _moe_case(skew)
    y, rows = moe_dropless(x, router, experts, top_k=2, dtype=jnp.float32)
    want = ref.experts(ref._sizes(cfg), {"router": router, **experts}, x,
                       "f32")
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert int(rows.sum()) == x.shape[0] * 2
    if skew:
        assert int(rows[0]) > 0.9 * x.shape[0]


def _sigmoid_shares(t: int = 40):
    """dots3-note-prev's expert layer at test widths, whole and as 8
    shares of 2 of its 16 experts: sigmoid scores, a selection bias, the
    picked scores renormalised, ONE shared expert every share computes
    alike (counted once). Its own plain reference. ``t`` rows."""
    dots3 = load_module(os.path.join(ROOT, "benchmark", "reference",
                                     "dots3-note-prev.py"))
    sizes = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "dots3-note-prev.json")))[
            "rehearsal"]["sizes"]
    k = jax.random.split(jax.random.key(2), 9)
    h, e, f = sizes["hidden_size"], 16, sizes["moe_intermediate_size"]
    x = jax.random.normal(k[0], (t, h))
    mp = {"router": jax.random.normal(k[1], (h, e)) * 0.2,
          "router_bias": jax.random.normal(k[2], (e,)) * 0.02,
          "gate": jax.random.normal(k[3], (e, h, f)) * 0.2,
          "up": jax.random.normal(k[4], (e, h, f)) * 0.2,
          "down": jax.random.normal(k[5], (e, f, h)) * 0.2,
          "shared": {"gate": jax.random.normal(k[6], (h, f)) * 0.2,
                     "up": jax.random.normal(k[7], (h, f)) * 0.2,
                     "down": jax.random.normal(k[8], (f, h)) * 0.2}}

    def z(held, first):
        return dots3._sizes(dict(
            sizes, n_routed_experts=held,
            published=dict(sizes["published"], n_routed_experts=e),
            share=dict(sizes["share"], first_expert=first)))

    shared = dots3.gated(mp["shared"], x, "f32")
    whole = dots3.routed(z(e, 0), mp, x, "f32") + shared
    kw = dict(top_k=sizes["num_experts_per_tok"], scores="sigmoid",
              select_bias=mp["router_bias"],
              scale=sizes["routed_scaling_factor"])

    def held(first):
        return {n: (v[first:first + 2] if n in ("gate", "up", "down") else v)
                for n, v in mp.items()}

    return x, whole, shared, 8, held, kw, (
        lambda first: dots3.routed(z(2, first), held(first), x, "f32"))


def _softmax_shares():
    """SDAR's: 8 experts held as 4 shares of 2, softmax scores."""
    x, router, experts, cfg = _moe_case(0.3)
    whole = ref.experts(ref._sizes(cfg), {"router": router, **experts}, x,
                        "f32")

    def held(first):
        return {"router": router,
                **{n: v[first:first + 2] for n, v in experts.items()}}

    return x, whole, 0.0, 4, held, dict(top_k=2), (
        lambda first: ref.experts(
            ref._sizes(dict(cfg, experts_held=2, first_expert=first)),
            held(first), x, "f32"))


def _sigmoid_shares_bounded():
    """The same 8 shares at 256 rows: each share's grouped matmuls run
    over ``pair_bound`` rows of its pairs, not over all of them."""
    case = _sigmoid_shares(256)
    pairs = 256 * case[5]["top_k"]
    assert pair_bound(pairs, 2, 16) == pairs // 4
    return case


@pytest.mark.parametrize(
    "case", [_softmax_shares, _sigmoid_shares, _sigmoid_shares_bounded],
    ids=["softmax_4_shares", "sigmoid_8_shares", "sigmoid_8_shares_bounded"])
def test_shares_of_the_experts_add_up_to_the_whole_layer(case):
    """The guide's share test. Each share routes over all the experts and
    computes its own experts' part; the parts, with what every share
    computes alike (a shared expert) counted once, add up to the uncut
    reference's layer, and a share's part is the reference's for the same
    share."""
    x, whole, alike, shares, held, kw, ref_part = case()
    total = alike
    for s in range(shares):
        mp = held(2 * s)
        part, rows = moe_dropless(x, mp["router"], mp, first_expert=2 * s,
                                  dtype=jnp.float32, **kw)
        np.testing.assert_allclose(part, ref_part(2 * s), rtol=1e-5,
                                   atol=1e-5)
        assert rows.shape == (2,)
        total = total + part
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=2e-5)


# ---- (b'') the pair buffer bounded by the held share ----------------------

def _routed_case(routing: str):
    """128 rows x top-2 of 16 experts, experts 4 and 5 held (1 in 8): 256
    pairs, ``pair_bound`` 128. The router is the identity on the first 16
    features, so a row's logits are written into it: ``uniform`` draws
    them, the others pick per row a pair of experts (the first held pick
    4, the second 5; absent picks 2 and 3). Returns ``(x, router,
    experts, cfg, held pairs)``."""
    t, e, h, f = 128, 16, 64, 32
    k = jax.random.split(jax.random.key(5), 5)
    if routing == "uniform":
        logits, n_held = jax.random.normal(k[0], (t, e)), None
    else:
        both, one = {"all_held": (t, 0), "at_bound": (64, 0),
                     "one_over": (64, 1), "none_held": (0, 0)}[routing]
        first = np.where(np.arange(t) < both + one, 4, 2)
        second = np.where(np.arange(t) < both, 5, 3)
        logits = (4.0 * jax.nn.one_hot(first, e)
                  + 3.0 * jax.nn.one_hot(second, e))
        n_held = 2 * both + one
    x = jnp.concatenate([logits, jax.random.normal(k[1], (t, h - e))], 1)
    router = jnp.zeros((h, e)).at[:e].set(jnp.eye(e))
    experts = {n: jax.random.normal(kk, s) * 0.2 for n, kk, s in (
        ("gate", k[2], (2, h, f)), ("up", k[3], (2, h, f)),
        ("down", k[4], (2, f, h)))}
    cfg = dict(CFG, num_experts=e, num_experts_per_tok=2, experts_held=2,
               first_expert=4)
    return x, router, experts, cfg, n_held


@pytest.mark.parametrize("routing", ["uniform", "all_held", "at_bound",
                                     "one_over", "none_held"])
def test_bounded_layer_is_the_dense_loop_and_the_whole_width(routing,
                                                             monkeypatch):
    """The layer over the first ``pair_bound`` rows of the sorted pairs
    (near-uniform routing; exactly as many held pairs as the bound; none
    at all) and its whole-width fallback (one held pair over the bound;
    a router that sends EVERY pick to a held expert) against the dense
    loop and against the layer with no bound: the same output, the same
    ``rows``. Dropless for any routing. float32: 1e-5."""
    x, router, experts, cfg, n_held = _routed_case(routing)

    def layer(x):                               # a trace of its own a call
        return moe_dropless(x, router, experts, top_k=2, first_expert=4,
                            dtype=jnp.float32)

    log = {}
    with moe_mod.tile_log(log):
        y, rows = jax.jit(layer)(x)
    assert log == {"pairs": 256, "bound": 128}
    want = ref.experts(ref._sizes(cfg), {"router": router, **experts}, x,
                       "f32")
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    monkeypatch.setattr(moe_mod, "pair_bound", lambda pairs, *_: pairs)
    whole, whole_rows = jax.jit(lambda x: layer(x))(x)
    np.testing.assert_allclose(y, whole, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(rows, whole_rows)
    if n_held is None:
        assert 0 < int(rows.sum()) < 128        # the bounded branch's case
    else:
        assert int(rows.sum()) == n_held
    if routing in ("all_held", "one_over"):     # the fallback IS that layer
        assert np.asarray(y).tobytes() == np.asarray(whole).tobytes()
    if routing == "none_held":
        assert not np.asarray(y).any()


@pytest.mark.parametrize("pairs,held,experts,want", [
    # dots3-note-prev's chunk: 1,024 rows x 8 picks, 32 of 256 held
    (8192, 32, 256, 2048),
    # ... and everything else that is served runs the parent's program:
    (192, 32, 256, 192),        # dots3's one-token step, 24 slots
    (8192, 128, 256, 8192),     # Kimi's chunk: 1 in 2 held
    (1024, 128, 256, 1024),     # Kimi's step, 128 slots
    (2048, 128, 128, 2048),     # SDAR's block step: every expert held
    (32768, 128, 128, 32768),   # SDAR's prefill
    (64, 4, 8, 64),             # the tiny decoders'
    # the rule's edges: twice the expected rows in whole tiles, engaged
    # where that is at most half of the pairs
    (4096, 32, 256, 1024),
    (1024, 32, 256, 256),
    (512, 32, 256, 128),
    (384, 32, 256, 128),
    (256, 32, 256, 128),
    (255, 32, 256, 255),
    (8192, 64, 256, 4096),      # 1 in 4 held: exactly half
    (8192, 65, 256, 8192),
])
def test_pair_bound_is_a_rule_on_shapes(pairs, held, experts, want):
    assert pair_bound(pairs, held, experts) == want


# ---- (b') the grouped matmuls' tile ---------------------------------------

BF16 = jnp.bfloat16


@pytest.mark.parametrize("pairs,k,n,dtype,want", [
    # what sdar-serve-backlog runs: 64 slots x 4 lanes x 8 experts a block
    # step, a 4,096-wide prefill x 8; gate/up then down
    (2048, 2048, 768, BF16, "128,2048,768"),
    (2048, 768, 2048, BF16, "128,768,2048"),
    (32768, 2048, 768, BF16, "128,2048,768"),
    (32768, 768, 2048, BF16, "128,768,2048"),
    # the prefill widths ROADMAP S10(a) brings: 1,024 and 2,048 tokens
    (8192, 2048, 768, BF16, "128,2048,768"),
    (8192, 768, 2048, BF16, "128,768,2048"),
    (16384, 2048, 768, BF16, "128,2048,768"),
    (16384, 768, 2048, BF16, "128,768,2048"),
    # the tile does not depend on the rows a group gets (8 to 256 read)
    (1024, 2048, 768, BF16, "128,2048,768"),
    (4096, 2048, 768, BF16, "128,2048,768"),
    # outside what was swept: XLA's own, exactly the parent's program
    (2048, 2000, 768, BF16, None),          # K not a multiple of 128
    (2048, 2048, 800, BF16, None),          # N not a multiple of 128
    # past 4,096 the matrix is over the budget: the whole K, N cut to the
    # widest part that fits (compiled for a described v5e, not timed) ...
    (2048, 8192, 768, BF16, "128,8192,256"),
    (2048, 768, 4224, BF16, "128,768,1408"),
    # ... read on the chip at what dots3-serve-longctx runs: 32 held
    # experts of [5120, 1536], a chunk's 8,192 pairs and their bound
    (8192, 5120, 1536, BF16, "128,5120,512"),
    (8192, 1536, 5120, BF16, "128,1536,1280"),
    (2048, 5120, 1536, BF16, "128,5120,512"),
    (2048, 1536, 5120, BF16, "128,1536,1280"),
    (192, 5120, 1536, BF16, None),          # its step: 24 slots x 8 picks
    (192, 1536, 5120, BF16, None),
    (2048, 5120, 1536, jnp.float32, None),
    (2048, 8320, 768, BF16, None),          # over 8,192
    (2048, 5120, 5120, BF16, "128,5120,512"),
    (2048, 2048, 768, jnp.float32, None),   # only bfloat16 was read
    (64, 2048, 768, BF16, None),            # fewer pairs than a tile
    (2000, 2048, 768, BF16, None),          # XLA wants m % tile rows == 0
    # wherever the whole matrix is over the budget, at any width, N is
    # cut (PR 41): read on the chip at what laguna-serve-mixed runs, 32
    # held experts of [3072, 1024] (15.7 MB double-buffered), a chunk's
    # 10,240 pairs and their bound; its step's 240 pairs keep XLA's own
    (10240, 3072, 1024, BF16, "128,3072,512"),
    (2560, 3072, 1024, BF16, "128,3072,512"),
    (2560, 1024, 3072, BF16, "128,1024,1536"),
    (240, 3072, 1024, BF16, None),
    (2048, 4096, 4096, BF16, "128,4096,512"),   # by the rule, not timed
    (24, 64, 32, BF16, None),               # sdar_moe_tiny's
])
def test_ragged_tiling_is_a_rule_on_shapes(pairs, k, n, dtype, want):
    assert ragged_tiling(pairs, k, n, dtype) == want


def _ragged_dots(text):
    """Per ``chlo.ragged_dot`` of a program lowered for the TPU, the tile
    it carries (None = XLA's own)."""
    import re
    return [(re.search(r'ragged_dot_tiling = "([^"]*)"', line) or [None, None]
             )[1] for line in text.splitlines() if "chlo.ragged_dot" in line]


@pytest.mark.parametrize("program,name,want", [
    ("block_step", "sdar_moe",
     ["128,2048,768", "128,2048,768", "128,768,2048"]),
    ("paged_prefill", "sdar_moe",
     ["128,2048,768", "128,2048,768", "128,768,2048"]),
    ("block_step", "sdar_moe_tiny", [None] * 3),
    ("paged_prefill", "sdar_moe_tiny", [None] * 3),
])
def test_lowered_programs_carry_the_tile_on_each_grouped_matmul(
        program, name, want):
    """The two served programs at the cell's widths and sizes (bfloat16,
    64 slots, 4,096-wide prefill), two layers, lowered for the TPU from
    shapes alone: gate, up and down of each layer carry
    ``ragged_dot_tiling`` (a prefill returns the pools, so its last
    layer's experts are dead code); at widths outside the rule none
    does (the parent's program)."""
    model = get_model(name, TrainConfig(
        model=name, num_layers=2, dtype="bfloat16", param_dtype="bfloat16"))
    c = model.cfg
    params = jax.eval_shape(model.init, jax.random.key(0))
    spec = jax.ShapeDtypeStruct
    slots, width, nb = (64, 4096, 34) if name == "sdar_moe" else (3, 32, 3)
    pool = spec((2, 1 + slots * nb, 128, c.kv_heads * c.head_dim),
                model.dtype)
    i32 = np.int32
    if program == "block_step":
        def fn(p, *a):
            return model.block_step(p, *a, attention="xla")
        args = (pool, pool, spec((slots, nb), i32),
                spec((slots, c.block_length), i32), spec((slots,), i32),
                spec((slots,), i32), spec((slots,), i32))
    else:
        def fn(p, *a):
            return model.paged_prefill(p, *a, attention="xla")
        args = (spec((1, width), i32), spec((1, width), i32), pool, pool,
                spec((width // 128 or 1,), i32))
    text = jax.jit(fn).trace(params, *args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert _ragged_dots(text) == want * (2 if program == "block_step" else 1)


def test_the_tile_changes_no_byte_off_the_tpu(monkeypatch):
    """A layer inside the rule (bfloat16, 128 wide, 128 and 512 pairs):
    the attribute is on the traced matmuls, the log says which tile, and
    the result is the one XLA's own tile gives, to the bit."""
    k = jax.random.split(jax.random.key(3), 5)
    router = jax.random.normal(k[1], (128, 4)) * 0.5
    experts = {"gate": jax.random.normal(k[2], (4, 128, 128)) * 0.1,
               "up": jax.random.normal(k[3], (4, 128, 128)) * 0.1,
               "down": jax.random.normal(k[4], (4, 128, 128)) * 0.1}

    def run(x):
        """(the tiles logged, the lowered text, the layer's results), each
        from a trace of its own"""
        def layer(x):
            return moe_dropless(x, router, experts, top_k=2)
        with moe_mod.tile_log() as tiles:
            text = jax.jit(layer).lower(x).as_text()
        return tiles, text, jax.jit(layer)(x)

    tile = "128,128,128"
    for rows in (64, 256):
        x = jax.random.normal(k[0], (rows, 128))
        tiles, text, with_tile = run(x)
        assert tiles == {"gate": tile, "up": tile, "down": tile}
        assert f'ragged_dot_tiling = "{tile}"' in text
        with monkeypatch.context() as mp:
            mp.setattr(moe_mod, "ragged_tiling", lambda *a: None)
            tiles, text, without = run(x)
        assert set(tiles.values()) == {"xla"}
        assert "ragged_dot_tiling" not in text
        for a, b in zip(with_tile, without):
            assert a.tobytes() == b.tobytes()


def test_artifact_keeps_the_tiles_and_the_engine_shows_them(artifact,
                                                            tmp_path):
    """A block 128 wide (inside the rule), exported and read back: both
    programs still carry the attribute, ``export.json`` names the tiles
    per program, ``/stats`` and the registry show them, and it generates.
    The tiny artifact (outside the rule) says ``xla`` and carries none."""
    from jax import export as jax_export
    cfg = DecoderBlockConfig(
        vocab_size=512, hidden=128, layers=2, heads=4, kv_heads=2,
        head_dim=32, experts=4, experts_per_token=2, expert_width=128,
        mask_id=511, max_len=512, denoising_steps=2)
    model = BlockDecoder(cfg)
    d = str(tmp_path / "wide")
    serving.export_generator(model, model.init(jax.random.key(1)), d,
                             ragged=True, stepwise=True, paged=True,
                             slots=16, block_size=16, prompt_len=256,
                             max_new_tokens=8, platforms=("cpu",))
    names = ("gate", "up", "down")
    # every width of the prefill is a program of its own
    tiled = {p: dict.fromkeys(names, "128,128,128")
             for p in ("prefill", "prefill_128", "prefill_64", "block_step")}
    own = {p: dict.fromkeys(names, "xla")
           for p in ("prefill", "prefill_16", "block_step")}
    for d_, want in ((d, tiled), (artifact, own)):
        meta = json.load(open(os.path.join(d_, "export.json")))
        assert meta["stepwise"]["block"]["moe_tiles"] == want
        for prog, tiles in want.items():
            with open(os.path.join(d_, prog + ".stablehlo"), "rb") as f:
                text = jax_export.deserialize(f.read()).mlir_module()
            tile = tiles["gate"]
            assert (f'ragged_dot_tiling = "{tile}"' in text) == (
                "ragged_dot_tiling" in text) == (tile != "xla")
    eng = GenerationEngine(serving.load_stepwise(d)).start()
    try:
        assert len(eng.generate(list(range(1, 40)), max_new=6)) == 6
        assert eng.stats()["moe_tiles"] == tiled
    finally:
        eng.close()


# ---- (c) the kernels ----------------------------------------------------

def _plain_attention(q, k, v, allowed):
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(allowed[None], s, -1e30), -1)
    return jnp.einsum("hqk,khd->qhd", p, v)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_paged_block_attention_grouped_kv(impl):
    """8 rows a KV head (2 query heads x 4 lanes) against a paged pool,
    windows ending inside the first, a middle and the last block, against
    plain attention over the gathered run. float32 inputs: 2e-6 (the
    online softmax sums in another order)."""
    k = jax.random.split(jax.random.key(0), 3)
    b, kvh, rows, d, n, bs = 3, 2, 8, 128, 9, 128
    q = jax.random.normal(k[0], (b, kvh, rows, d))
    kp = jax.random.normal(k[1], (n, bs, kvh * d))
    vp = jax.random.normal(k[2], (n, bs, kvh * d))
    bt = jnp.array([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
    last = jnp.array([383, 131, 3], jnp.int32)
    got = decode_mod.paged_block_attention(q, kp, vp, block_tables=bt,
                                           last=last, impl=impl)
    for i in range(b):
        run = kp[bt[i]].reshape(-1, kvh, d), vp[bt[i]].reshape(-1, kvh, d)
        allowed = (jnp.arange(3 * bs) <= last[i])[None, :].repeat(rows, 0)
        for h in range(kvh):
            want = _plain_attention(
                q[i, h][:, None], run[0][:, h:h + 1], run[1][:, h:h + 1],
                allowed)[:, 0]
            np.testing.assert_allclose(got[i, h], want, rtol=2e-5,
                                       atol=2e-6)
    with pytest.raises(ValueError):
        decode_mod.paged_block_attention(q[..., :64], kp, vp,
                                         block_tables=bt, last=last)


@pytest.mark.parametrize("seq,kw", [(512, dict(block_q=256, block_k=256)),
                                    (40, {})])
def test_flash_causal_block_4(seq, kw):
    """``flash_fwd`` under the block-causal mask (and the XLA fallback at
    a shape no tile takes) against plain attention under
    ``floor(j / 4) <= floor(i / 4)``; float32: 2e-6."""
    k = jax.random.split(jax.random.key(0), 3)
    q, kk, v = [jax.random.normal(x, (1, seq, 2, 128)) for x in k]
    got = flash_mod.flash_attention(q, kk, v, causal=True, causal_block=4,
                                    **kw)
    pos = jnp.arange(seq)
    want = _plain_attention(q[0], kk[0], v[0],
                            (pos[None, :] // 4) <= (pos[:, None] // 4))
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=2e-6)
    for bad in (dict(causal_block=3), dict(causal_block=4, causal=False)):
        with pytest.raises(ValueError):
            flash_mod.flash_attention(q, kk, v, **{"causal": True, **bad})


def test_defaults_leave_the_kernels_others_call_byte_identical():
    """``causal_block`` 1 is the causal mask: the same jaxpr, the same
    lowered program and the same bytes as a call that does not name it.
    ``paged_decode_attention`` (GPT-2's, one head count; its pool flat
    like this decoder's since PR 31) agrees with its XLA reference on
    float32."""
    k = jax.random.split(jax.random.key(0), 4)
    q, kk, v = [jax.random.normal(x, (2, 256, 2, 64)).astype(jnp.bfloat16)
                for x in k[:3]]

    def old(q, k_, v_):
        return flash_mod.flash_attention(q, k_, v_, causal=True)

    def new(q, k_, v_):
        return flash_mod.flash_attention(q, k_, v_, causal=True,
                                         causal_block=1)

    assert str(jax.make_jaxpr(old)(q, kk, v)) == str(
        jax.make_jaxpr(new)(q, kk, v))
    assert jax.jit(old).lower(q, kk, v).as_text() == jax.jit(new).lower(
        q, kk, v).as_text().replace("jit_new", "jit_old")
    assert (old(q, kk, v) == new(q, kk, v)).all()
    g_old = jax.grad(lambda a: old(a, kk, v).astype(jnp.float32).sum())(q)
    g_new = jax.grad(lambda a: new(a, kk, v).astype(jnp.float32).sum())(q)
    assert (g_old == g_new).all()
    qd = jax.random.normal(k[3], (2, 12, 64))
    pool = jax.random.normal(k[0], (5, 128, 12 * 64))
    kw = dict(block_tables=jnp.array([[1, 2], [3, 0]], jnp.int32),
              pos=jnp.array([200, 17]), pad=jnp.array([0, 0]))
    a = decode_mod.paged_decode_attention(qd, pool, pool, impl="pallas",
                                          **kw)
    b = decode_mod.paged_decode_attention(qd, pool, pool, impl="xla", **kw)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


# ---- (d) weights as arguments -----------------------------------------

def test_weights_as_arguments_round_trip(f32, artifact, tmp_path,
                                         monkeypatch):
    """Above the byte rule the artifact keeps a checkpoint in the storage
    dtype and its programs take the tree as an argument: exported,
    loaded, one copy on the device (the loader's), and it generates what
    the baked artifact generates."""
    bf, params = build("bfloat16")
    monkeypatch.setattr(serving, "BAKE_LIMIT_BYTES", 1000)
    d = str(tmp_path / "args")
    serving.export_generator(bf, params, d, ragged=True, stepwise=True,
                             paged=True, slots=3, block_size=16,
                             prompt_len=32, max_new_tokens=16,
                             platforms=("cpu",))
    meta = json.load(open(os.path.join(d, "export.json")))
    assert meta["weights"] == "checkpoint"
    assert not os.path.exists(os.path.join(d, "model.stablehlo"))
    index = json.load(open(os.path.join(d, "params", "params.json")))
    assert {e["dtype"] for e in index} == {"bfloat16"}
    on_disk = sum(os.path.getsize(os.path.join(d, "params", e["file"]))
                  for e in index)
    assert meta["param_bytes"] <= on_disk < meta["param_bytes"] + 200 * len(
        index)
    # the programs hold no weights: they are small beside the checkpoint
    assert os.path.getsize(os.path.join(d, "block_step.stablehlo")) < 0.2 * (
        meta["param_bytes"])
    del params
    sw = serving.load_stepwise(d)
    loaded = jax.tree_util.tree_leaves(sw.params)
    assert sum(x.nbytes for x in loaded) == meta["param_bytes"]
    eng = GenerationEngine(sw).start()
    try:
        held = [v for v in vars(eng).values()
                if isinstance(v, (dict, jax.Array)) and v is not eng._pool]
        assert not any(isinstance(x, jax.Array) and x.nbytes > 4096
                       for v in held for x in jax.tree_util.tree_leaves(v))
        prompt = np.random.RandomState(0).randint(0, 500, 13).tolist()
        got = eng.generate(prompt, max_new=9)
    finally:
        eng.close()
    # the same weights baked in (under the rule): the same tokens
    monkeypatch.undo()
    bf2, params2 = build("bfloat16")
    d2 = str(tmp_path / "baked")
    serving.export_generator(bf2, params2, d2, ragged=True, stepwise=True,
                             paged=True, slots=3, block_size=16,
                             prompt_len=32, max_new_tokens=16,
                             platforms=("cpu",))
    assert json.load(open(os.path.join(d2, "export.json")))[
        "weights"] == "baked"
    sw2 = serving.load_stepwise(d2)
    assert sw2.params is None
    eng2 = GenerationEngine(sw2).start()
    try:
        assert eng2.generate(prompt, max_new=9) == got
    finally:
        eng2.close()


def test_gpt_artifact_keeps_its_baked_form(tmp_path):
    gpt = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    params = gpt.init(jax.random.key(0))
    d = str(tmp_path / "gpt")
    serving.export_generator(gpt, params, d, ragged=True, stepwise=True,
                             paged=True, slots=2, block_size=16,
                             prompt_len=16, max_new_tokens=8,
                             platforms=("cpu",))
    meta = json.load(open(os.path.join(d, "export.json")))
    assert "weights" not in meta and "block" not in meta["stepwise"]
    assert sorted(f for f in os.listdir(d)) == [
        "decode.stablehlo", "export.json", "model.stablehlo",
        "prefill.stablehlo"]
    sw = serving.load_stepwise(d)
    assert sw.params is None and sw.block is None
    with pytest.raises(ValueError, match="no block-step program"):
        sw.block_step({})


# ---- HTTP: the response's fields and /stats ---------------------------

def test_generate_over_http_reports_forwards_and_unmask_steps(f32,
                                                              artifact):
    from distributed_tensorflow_example_tpu.serving_http import PredictServer
    _, params = f32
    srv = PredictServer(artifact, port=0, max_queue=8)
    srv.start()
    try:
        prompt = np.random.RandomState(9).randint(0, 500, 10).tolist()
        base = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            f"{base}/v1/models/{srv.name}:generate",
            data=json.dumps({"inputs": {"input_ids": [prompt]},
                             "max_new": 6}).encode(),
            headers={"Content-Type": "application/json"})
        ans = json.loads(urllib.request.urlopen(req, timeout=120).read())
        want, when, forwards = simulate(params, prompt, 6)
        assert ans["generations"][0] == want
        assert ans["timings"][0]["forwards"] == forwards
        assert ans["timings"][0]["unmask_step"] == when
        stats = json.loads(urllib.request.urlopen(f"{base}/stats",
                                                  timeout=30).read())
        flat = json.dumps(stats)
        for key in ("block_steps", "denoise_forwards", "commit_forwards",
                    "tokens_committed", "moe_rows", "moe_tiles",
                    "moe_max_expert_load_ratio"):
            assert f'"{key}"' in flat
    finally:
        srv.stop(drain=False)

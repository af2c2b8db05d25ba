"""A decoder with a kind a layer (``models/decoder.py``, ``linear_attn``:
Kimi-Linear-48B-A3B-Instruct's layers at test widths) against its plain
reference (``benchmark/reference/kimi-linear-48b-a3b.py``), seeded random
weights: KDA's three computations, MLA absorbed over a paged latent
pool, the sigmoid router with its shared expert, the shares of a layer,
chunked prefill and one-token decode through the state, the artifact
with its state specs, and the engine."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights_by_range                     # noqa: E402
from benchmark.manifest import load_module                 # noqa: E402
from distributed_tensorflow_example_tpu import serving     # noqa: E402
from distributed_tensorflow_example_tpu.config import TrainConfig  # noqa: E402
from distributed_tensorflow_example_tpu.models import get_model   # noqa: E402
from distributed_tensorflow_example_tpu.models.decoder import (  # noqa: E402
    BlockDecoder, DecoderBlockConfig)
from distributed_tensorflow_example_tpu.ops import kda, mla  # noqa: E402
from distributed_tensorflow_example_tpu.ops.moe import (  # noqa: E402
    moe_dropless)
from distributed_tensorflow_example_tpu.serving_batch import (  # noqa: E402
    GenerationEngine)

ref = load_module(os.path.join(ROOT, "benchmark", "reference",
                               "kimi-linear-48b-a3b.py"))
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                     "kimi-linear-48b-a3b.json")))
CFG = CONFIG["rehearsal"]["sizes"]
#: the uncut twin: every expert and the whole vocabulary on one chip
WHOLE = dict(CFG, num_experts=8, vocab_size=512)
SLOTS, BS, CHUNK, PROMPT, NEW = 3, 16, 32, 96, 24


def build(dtype: str, cfg=CFG, seed: int = 7, **share):
    model = get_model("kimi_linear_tiny", TrainConfig(
        model="kimi_linear_tiny", dtype=dtype, param_dtype=dtype))
    for k, v in (share or CONFIG["rehearsal"]["model_cfg"]).items():
        setattr(model.cfg, k, v)
    params = weights_by_range.make_params(ref.param_spec(cfg), seed, dtype)
    return model, params


@pytest.fixture(scope="module")
def f32():
    return build("float32")


def test_registry_builds_the_layers_from_a_description():
    big = get_model("kimi_linear", TrainConfig(model="kimi_linear",
                                               num_layers=5))
    c = big.cfg
    assert (c.hidden, c.heads, c.head_dim, c.experts, c.experts_per_token,
            c.expert_width, c.dense_width, c.vocab_size, c.kv_lora_rank,
            c.latent_dim, c.latent_row) == (
                2304, 32, 128, 256, 8, 1024, 9216, 163840, 512, 576, 640)
    assert [c.mixer(i) for i in range(5)] == ["kda", "kda", "kda", "mla",
                                              "kda"]
    specs = big.state_specs(slots=128, num_blocks=17409, block_size=128)
    assert specs["cache_latent"]["shape"] == [1, 17409, 128, 640]
    assert specs["cache_state"]["shape"] == [4, 128, 32, 128, 128]
    assert specs["cache_conv"]["shape"] == [4, 128, 3 * 3 * 4096]
    tiny = DecoderBlockConfig.kimi_linear_tiny().__dict__
    for bad in (dict(block_length=4), dict(linear_attn=False),
                dict(router_scores="tanh"),
                dict(first_vocab=200, vocab_held=384)):
        with pytest.raises(ValueError):
            BlockDecoder(DecoderBlockConfig(**{**tiny, **bad}))
    # the program's tree is the reference's, leaf for leaf
    model, params = build("bfloat16")
    shapes = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)),
                                    jax.eval_shape(model.init,
                                                   jax.random.key(0)))
    assert shapes == jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), params)
    a = np.asarray(params["layers"]["0"]["kda"]["a_log"], np.float64)
    dt = np.logaddexp(0, np.asarray(params["layers"]["0"]["kda"]["dt_bias"],
                                    np.float64))
    assert 1.0 <= np.exp(a).min() and np.exp(a).max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001


# ---- (a) KDA: the chunked scan, the token recurrence, one token -------

def _kda_inputs(t, h=3, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 8)
    q = kda.l2norm(jax.random.normal(ks[0], (t, h, d))) * d ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (t, h, d)))
    v = jax.random.normal(ks[2], (t, h, d))
    # the family's whole range: a channel may decay by e^-8 a token
    log_a = -jnp.exp(jax.random.uniform(ks[3], (t, h, d), minval=0.0,
                                        maxval=np.log(16.0))) * jnp.exp(
        jax.random.uniform(ks[4], (t, h, d), minval=np.log(1e-3),
                           maxval=np.log(0.5)))
    b = jax.nn.sigmoid(jax.random.normal(ks[5], (t, h)))
    s0 = jax.random.normal(ks[6], (h, d, d))
    return s0, q, k, v, log_a, b


@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_kda_chunk_scan_is_the_token_recurrence(chunk):
    """A state carried in, 256 tokens, decays over the family's range
    (a pair's ``exp(g_t) * exp(-g_i)`` would overflow float32)."""
    args = _kda_inputs(256)
    o_r, s_r = kda.kda_recurrent(*args)
    o_c, s_c = jax.jit(lambda *a: kda.kda_chunk_scan(*a, chunk=chunk))(*args)
    assert np.isfinite(np.asarray(o_c)).all()
    np.testing.assert_allclose(o_c, o_r, atol=2e-6)
    np.testing.assert_allclose(s_c, s_r, atol=2e-5)


def test_kda_padding_leaves_the_state_and_one_step_is_the_next_token():
    s0, q, k, v, log_a, b = _kda_inputs(64, seed=1)
    _, s_40 = kda.kda_recurrent(s0, q[:40], k[:40], v[:40], log_a[:40],
                                b[:40])
    valid = jnp.arange(64) < 40
    _, s_pad = kda.kda_chunk_scan(
        s0, q, k, v, jnp.where(valid[:, None, None], log_a, 0.0),
        jnp.where(valid[:, None], b, 0.0), chunk=32)
    np.testing.assert_allclose(s_pad, s_40, atol=2e-6)
    # the one-token update of two rows, one of them not alive
    o_r, s_r = kda.kda_recurrent(s_40, q[40:41], k[40:41], v[40:41],
                                 log_a[40:41], b[40:41])
    two = lambda x: jnp.stack([x[40], x[40]])            # noqa: E731
    live = jnp.array([True, False])
    a = jnp.where(live[:, None, None], jnp.exp(two(log_a)), 1.0)
    o, s = kda.kda_step(jnp.stack([s_40, s_40]), two(q), two(k), two(v), a,
                        jnp.where(live[:, None], two(b), 0.0))
    np.testing.assert_allclose(o[0], o_r[0], atol=1e-6)
    np.testing.assert_allclose(s[0], s_r, atol=1e-6)
    assert np.array_equal(np.asarray(s[1]), np.asarray(s_40))


def test_kda_steps_on_one_layer_of_a_stacked_state_are_the_recurrence():
    """What the decode step does with a donated ``[L, rows, H, d, d]``
    state: five one-token updates of layer 1 are the token recurrence,
    layer 0 and a row that is not alive stay as they were to the bit."""
    n_layers, rows, h, d, steps = 2, 3, 4, 32, 5
    ks_ = jax.random.split(jax.random.key(12), 6)
    s_all = jax.random.normal(ks_[0], (n_layers, rows, h, d, d))
    shape = (steps, rows, h, d)
    q = kda.l2norm(jax.random.normal(ks_[1], shape)) * d ** -0.5
    k = kda.l2norm(jax.random.normal(ks_[2], shape))
    v = jax.random.normal(ks_[3], shape)
    log_a = -jax.random.uniform(ks_[4], shape)
    b = jax.nn.sigmoid(jax.random.normal(ks_[5], shape[:3]))
    live = jnp.array([True, False, True])

    def step(s_all, q, k, v, log_a, b):
        a = jnp.where(live[:, None, None], jnp.exp(log_a), 1.0)
        o, s_new = kda.kda_step(s_all[1], q, k, v, a,
                                jnp.where(live[:, None], b, 0.0))
        return o, s_all.at[1].set(s_new)

    fn = jax.jit(step, donate_argnums=0)
    before = np.asarray(s_all)
    outs = []
    for t in range(steps):
        o, s_all = fn(s_all, q[t], k[t], v[t], log_a[t], b[t])
        outs.append(o)
    for row in (0, 2):
        o_r, s_r = kda.kda_recurrent(before[1, row], q[:, row], k[:, row],
                                     v[:, row], log_a[:, row], b[:, row])
        np.testing.assert_allclose(jnp.stack(outs)[:, row], o_r, atol=2e-6)
        np.testing.assert_allclose(s_all[1, row], s_r, atol=2e-6)
    assert np.array_equal(np.asarray(s_all[0]), before[0])
    assert np.array_equal(np.asarray(s_all[1, 1]), before[1, 1])


def test_causal_conv_carries_its_tail():
    x = jax.random.normal(jax.random.key(2), (24, 8))
    w = jax.random.normal(jax.random.key(3), (4, 8))
    whole = ref.conv4(x, w)
    y1, tail = kda.causal_conv(x[:16], jnp.zeros((3, 8)), w, 10)
    np.testing.assert_allclose(y1[:10], whole[:10], atol=1e-6)
    y2, _ = kda.causal_conv(x[10:], tail, w)
    np.testing.assert_allclose(y2, whole[10:], atol=1e-6)


# ---- (b) MLA: absorbed, over a paged latent pool -----------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_absorbed_mla_decode_over_the_pool_is_the_full_attention(impl):
    """One query a slot against latent rows behind a block table, W_kvb
    folded into query and output, against the reference's unabsorbed
    attention over the same rows (its last row)."""
    h, nope, pe, vd, rank, row = 8, 16, 8, 16, 128, 256
    bs, nb, n = 128, 8, 20
    ks = jax.random.split(jax.random.key(4), 6)
    lens = [5, 300, 1024]
    pool = jnp.zeros((n, bs, row))
    bt = np.zeros((3, nb), np.int32)
    w_kvb = jax.random.normal(ks[0], (rank, h, nope + vd)) * rank ** -0.5
    free = iter(range(1, n))
    outs, wants = [], []
    qs = jax.random.normal(ks[1], (3, h, nope + pe))
    for b, t in enumerate(lens):
        c_kv = jax.random.normal(jax.random.fold_in(ks[2], b), (t, rank))
        k_pe = jax.random.normal(jax.random.fold_in(ks[3], b), (t, pe))
        lat = jnp.concatenate([c_kv, k_pe, jnp.zeros((t, row - rank - pe))],
                              axis=-1)
        blocks = [next(free) for _ in range(-(-t // bs))]
        bt[b, :len(blocks)] = blocks
        padded = jnp.pad(lat, ((0, len(blocks) * bs - t), (0, 0)))
        pool = pool.at[jnp.array(blocks)].set(padded.reshape(-1, bs, row))
        kv = jnp.einsum("tc,chd->thd", c_kv, w_kvb)
        sc = (jnp.einsum("hd,thd->ht", qs[b, :, :nope], kv[..., :nope])
              + jnp.einsum("hd,td->ht", qs[b, :, nope:], k_pe)
              ) / np.sqrt(nope + pe)
        wants.append(jnp.einsum("ht,thd->hd", jax.nn.softmax(sc, -1),
                                kv[..., nope:]))
    scale = (nope + pe) ** -0.5
    q_lat = jnp.einsum("bhd,chd->bhc", qs[..., :nope] * scale,
                       w_kvb[..., :nope])
    q_abs = jnp.concatenate([q_lat, qs[..., nope:] * scale,
                             jnp.zeros((3, h, row - rank - pe))], axis=-1)
    ctx = mla.mla_decode_attention(q_abs, pool, block_tables=bt,
                                   last=jnp.array(lens) - 1, rank=rank,
                                   impl=impl)
    got = jnp.einsum("bhc,chd->bhd", ctx, w_kvb[..., nope:])
    np.testing.assert_allclose(got, jnp.stack(wants), atol=2e-5)


def test_mla_prefill_tiles_are_the_full_causal_attention():
    h, nope, pe, vd, rank, row, bs, t = 4, 16, 8, 16, 32, 128, 16, 32
    ks = jax.random.split(jax.random.key(5), 4)
    total = 3 * t
    lat = jnp.concatenate(
        [jax.random.normal(ks[0], (total, rank + pe)),
         jnp.zeros((total, row - rank - pe))], axis=-1)
    w_kvb = jax.random.normal(ks[1], (rank, h, nope + vd)) * rank ** -0.5
    q = jax.random.normal(ks[2], (total, h, nope + pe))
    table = jnp.array([4, 2, 7, 1, 5, 3, 0, 0], jnp.int32)
    pool = jnp.zeros((9, bs, row)).at[table[:6]].set(
        lat.reshape(6, bs, row))
    kv = jnp.einsum("tc,chd->thd", lat[:, :rank], w_kvb)
    sc = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
          + jnp.einsum("qhd,kd->hqk", q[..., nope:], lat[:, rank:rank + pe])
          ) / np.sqrt(nope + pe)
    causal = jnp.tril(jnp.ones((total, total), bool))
    want = jnp.einsum("hqk,khd->qhd",
                      jax.nn.softmax(jnp.where(causal, sc, -1e30), -1),
                      kv[..., nope:])
    for start in (0, t, 2 * t):
        got = mla.mla_prefill_attention(
            q[start:start + t], pool, table, start, w_kvb, rank=rank,
            nope=nope, pe=pe, v_dim=vd, scale=(nope + pe) ** -0.5)
        np.testing.assert_allclose(got, want[start:start + t], atol=2e-5)


# ---- (c) the expert layer ----------------------------------------------

def _ffn_rows(seed=6, t=40):
    return jax.random.normal(jax.random.key(seed), (t, CFG["hidden_size"]))


def test_sigmoid_router_with_bias_scale_and_shared_expert_is_the_loop(f32):
    """Sigmoid scores, a bias that selects and does not weigh (made large
    enough here to change the choice), renormalised top-k times the
    scaling factor, one shared expert: the sorted grouped matmuls against
    the reference's dense loop."""
    model, params = f32
    z = ref._sizes(CFG)
    mp = dict(params["layers"]["1"]["moe"])
    mp["router_bias"] = mp["router_bias"] * 40.0
    m = _ffn_rows()
    plain = jax.lax.top_k(jax.nn.sigmoid(m @ mp["router"]), 2)[1]
    biased = jax.lax.top_k(jax.nn.sigmoid(m @ mp["router"])
                           + mp["router_bias"], 2)[1]
    assert not np.array_equal(np.asarray(plain), np.asarray(biased))
    lp = {"ffn_norm": jnp.ones((CFG["hidden_size"],)), "moe": mp}
    got, rows = model._ffn_of(1, lp, m)
    n = ref.rms(m, lp["ffn_norm"], z["eps"])
    want = m + ref.routed(z, mp, n, "f32") + ref.gated(mp["shared"], n,
                                                       "f32")
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(rows.sum()) == int(np.isin(np.asarray(biased),
                                          np.arange(4)).sum())


def test_the_shares_of_a_layer_add_up():
    """Two shares of half the experts each, the shared expert and the
    residual counted once, give the uncut reference's layer."""
    _, whole = build("float32", WHOLE, experts_held=0, vocab_held=0)
    lp = whole["layers"]["2"]
    z = ref._sizes(WHOLE)
    m = _ffn_rows(seed=8)
    n = ref.rms(m, lp["ffn_norm"], z["eps"])
    want = m + ref.routed(z, lp["moe"], n, "f32") + ref.gated(
        lp["moe"]["shared"], n, "f32")
    total = m + ref.gated(lp["moe"]["shared"], n, "f32")
    for first in (0, 4):
        half = {k: (v[first:first + 4] if k in ("gate", "up", "down")
                    else v) for k, v in lp["moe"].items()}
        y, rows = moe_dropless(
            n, half["router"], half, top_k=2, first_expert=first,
            dtype=jnp.float32, scores="sigmoid",
            select_bias=half["router_bias"], scale=z["scale"])
        # the reference given the same share computes the same part
        zs = ref._sizes(dict(CFG, share={"first_expert": first}))
        np.testing.assert_allclose(y, ref.routed(zs, half, n, "f32"),
                                   atol=2e-5)
        total = total + y
    np.testing.assert_allclose(total, want, atol=3e-5)


# ---- (d) chunked prefill, then decode, through the state ---------------

def _empty_state(model, dtype=None):
    specs = model.state_specs(slots=SLOTS, num_blocks=1 + SLOTS * 8,
                              block_size=BS)
    return {k: jnp.zeros(v["shape"], v["dtype"]) for k, v in specs.items()}


def _serve_by_hand(model, params, toks, p, slot=1, impl="xla", chunk=CHUNK):
    """Chunked prefill of ``toks[:p]`` then one decode step a further
    token, through the state: logits at every position, and the state."""
    state = _empty_state(model)
    table = np.zeros((SLOTS, 8), np.int32)
    table[slot] = 1 + slot * 8 + np.arange(8)
    fn = jax.jit(lambda st, ids, n, start, cb: model.prefill_chunk(
        params, st, ids, n, start, slot, table[slot], cb, with_logits=True,
        kda_chunk=16))
    rows = []
    for start in range(0, p, chunk):
        n = min(chunk, p - start)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :n] = toks[start:start + n]
        cb = np.zeros((chunk // BS,), np.int32)
        need = -(-p // BS)
        for j in range(chunk // BS):
            if start // BS + j < need:
                cb[j] = table[slot, start // BS + j]
        out = fn(state, ids, n, start, cb)
        state = {k: out[k] for k in state}
        rows.append(np.asarray(out["logits"], np.float32)[:n])
    first = int(out["ids"][0])
    step = jax.jit(lambda st, tok, pos, alive: model.decode_step(
        params, st, table, tok, pos, alive, attention=impl,
        with_logits=True))
    for t in range(p, len(toks)):
        tok, pos, alive = (np.zeros(SLOTS, np.int32) for _ in range(3))
        tok[slot], pos[slot], alive[slot] = toks[t], t, 1
        out = step(state, tok, pos, alive)
        state = {k: out[k] for k in state}
        rows.append(np.asarray(out["logits"], np.float32)[slot][None])
    return np.concatenate(rows), first, state


@pytest.mark.parametrize("p", [70, 64, 5])
def test_chunked_prefill_then_decode_is_the_reference_forward_f32(f32, p):
    """float32 program against the float32 reference over 90 tokens: the
    prompt in chunks of 32 (a prompt that ends inside a chunk, on a chunk
    boundary, inside the first), the rest one token a step. Tolerance
    2e-5 on logits of magnitude ~2: float32 rounding through five layers
    in another order."""
    model, params = f32
    toks = np.random.RandomState(p).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, first, state = _serve_by_hand(model, params, toks, p)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert first == int(np.argmax(want[p - 1]))
    # the other slots' recurrent rows were never touched
    assert not np.asarray(state["cache_state"][:, 0]).any()
    assert not np.asarray(state["cache_conv"][:, 2]).any()


def test_chunked_prefill_then_decode_in_bfloat16_is_near_it():
    """bfloat16 storage and operands against the float32 reference on
    the same (bfloat16-rounded) weights: mean gap of the served argmax
    below the reference's best logit under 0.02 (logits spread ~2)."""
    model, params = build("bfloat16")
    toks = np.random.RandomState(3).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, _, _ = _serve_by_hand(model, params, toks, 70, impl="xla")
    gap = want.max(-1) - want[np.arange(90), got.argmax(-1)]
    assert gap.mean() < 0.02 and np.abs(got - want).mean() < 0.05


def test_a_state_not_carried_or_not_zeroed_shows_in_the_logits(f32):
    """What the cell's two planted faults do, by hand: the recurrent rows
    dropped at a chunk boundary, or left from another request, move the
    logits far beyond rounding."""
    model, params = f32
    toks = np.random.RandomState(9).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    _, _, dirty = _serve_by_hand(model, params, toks, 70)
    fn = jax.jit(lambda st, ids, start, cb: model.prefill_chunk(
        params, st, ids, 32, start, 1, jnp.arange(9, 17), cb,
        with_logits=True, kda_chunk=16))
    for fault in ("dropped", "dirty"):
        state = _empty_state(model) if fault == "dropped" else dirty
        rows = []
        for start in (0, 32):
            out = fn(state, toks[None, start:start + 32], start,
                     np.array([9, 10]) + start // BS)
            state = {k: out[k] for k in state}
            if fault == "dropped":
                state["cache_state"] = jnp.zeros_like(state["cache_state"])
            rows.append(np.asarray(out["logits"]))
        got = np.concatenate(rows)
        bad = slice(32, 64) if fault == "dropped" else slice(0, 64)
        assert np.abs(got[bad] - want[bad]).max() > 1e-2


# ---- (e) the artifact and the engine ------------------------------------

@pytest.fixture(scope="module")
def artifact(f32, tmp_path_factory):
    model, params = f32
    out = str(tmp_path_factory.mktemp("kimi_tiny"))
    serving.export_generator(
        model, params, out, ragged=True, stepwise=True, paged=True,
        slots=SLOTS, block_size=BS, prompt_len=PROMPT, max_new_tokens=NEW,
        prefill_chunk=CHUNK, platforms=("cpu",))
    return out


def simulate(params, prompt, max_new):
    """The reference's cacheless greedy generation."""
    seq = list(prompt)
    fwd = jax.jit(lambda x: ref.logits(CFG, params, x))
    for _ in range(max_new):
        x = np.zeros((PROMPT + NEW,), np.int32)
        x[:len(seq)] = seq
        seq.append(int(np.argmax(np.asarray(fwd(jnp.asarray(x)))[
            len(seq) - 1])))
    return seq[len(prompt):]


def test_artifact_round_trip_records_its_state_specs(artifact, f32):
    model, _ = f32
    meta = json.load(open(os.path.join(artifact, "export.json")))
    sm = meta["stepwise"]
    assert not os.path.exists(os.path.join(artifact, "prefill.stablehlo"))
    assert not os.path.exists(os.path.join(artifact, "model.stablehlo"))
    assert sm["prefill_chunk"] == CHUNK and sm["paged"]
    st = sm["state"]
    assert st["mixers"] == ["kda", "kda", "kda", "mla", "kda"]
    assert st["ffns"] == ["dense", "moe", "moe", "moe", "moe"]
    assert st["specs"] == json.loads(json.dumps(model.state_specs(
        slots=SLOTS, num_blocks=sm["num_blocks"], block_size=BS)))
    assert sm["pool_shape"] == st["specs"]["cache_latent"]["shape"]
    assert (st["experts"], st["experts_held"], st["vocab_held"]) == (
        8, 4, 384)
    sw = serving.load_stepwise(artifact)
    pool = sw.make_pool()
    assert {k: list(v.shape) for k, v in pool.items()} == {
        k: v["shape"] for k, v in st["specs"].items()}
    # zeroing a slot touches that slot's recurrent rows and nothing else
    ones = {k: jnp.ones_like(v) for k, v in pool.items()}
    z = sw.zero_slot(ones, 1)
    assert not np.asarray(z["cache_state"][:, 1]).any()
    assert not np.asarray(z["cache_conv"][:, 1]).any()
    assert np.asarray(z["cache_state"][:, 0]).all()
    assert np.asarray(z["cache_latent"]).all()
    with pytest.raises(ValueError, match="scheduler"):
        serving.load_servable(artifact)({"input_ids": np.zeros((1, 4))})


def test_engine_generates_what_the_reference_generates(artifact, f32):
    """Requests of unlike lengths (inside one chunk, over three, on a
    chunk boundary) through chunked prefill and the shared one-token
    step, more requests than slots so that slots are reused: each gives
    the reference's greedy tokens, as it does alone."""
    _, params = f32
    rs = np.random.RandomState(11)
    lens = [5, 70, 64, 33, 96, 17]
    prompts = [rs.randint(0, 384, n).tolist() for n in lens]
    new = [NEW, 9, 16, NEW, 12, 7]
    want = [simulate(params, p, k) for p, k in zip(prompts, new)]
    eng = GenerationEngine(serving.load_stepwise(artifact)).start()
    try:
        assert eng.prefill_chunk_tokens == CHUNK
        assert eng.cache.prefix is None
        handles = [eng.submit(p, max_new=k)
                   for p, k in zip(prompts, new)]
        got = [h.result(timeout=300) for h in handles]
        assert got == want
        st = eng.stats()
        # unlike lengths batched in one step; every slot was reused
        assert st["steps_shared"] > 1.5
        assert st["admissions"] == 6 > SLOTS
        assert st["prefill_chunks"] == sum(-(-n // CHUNK) for n in lens)
        assert st["prefill_chunk_tokens_total"] == sum(lens)
        assert st["state"]["mixers"][3] == "mla"
        assert st["state_bytes"] == SLOTS * 4 * (
            4 * 16 * 16 + 3 * 3 * 4 * 16) * 4
        assert st["latent_pool_bytes"] == int(np.prod(
            st["pool_shape"])) * 4
        assert st["moe_rows"] == 4 * 2 * (sum(lens) + st[
            "decode_slot_steps"])
        # alone, after the others: the same tokens from a reused slot
        alone = eng.submit(prompts[1], max_new=new[1]).result(timeout=300)
        assert alone == want[1]
    finally:
        eng.close()


def test_what_the_artifact_refuses_is_said(artifact, f32):
    model, params = f32
    sw = serving.load_stepwise(artifact)
    with pytest.raises(ValueError, match="rewind"):
        GenerationEngine(sw, spec_tokens=2)
    with pytest.raises(ValueError, match="chunk"):
        GenerationEngine(sw, prefill_chunk_tokens=16)
    eng = GenerationEngine(sw, prefix_cache=True)
    assert eng.cache.prefix is None
    with pytest.raises(ValueError, match="greedy"):
        eng.submit([1, 2, 3], temperature=0.7)
    for bad in (dict(spec_tokens=2), dict(temperature=0.5),
                dict(weight_quant="int8"), dict(kv_cache_dtype="int8"),
                dict(prefill_chunk=0), dict(prefill_chunk=24)):
        kw = dict(ragged=True, stepwise=True, paged=True, slots=SLOTS,
                  block_size=BS, prompt_len=PROMPT, max_new_tokens=NEW,
                  prefill_chunk=CHUNK, platforms=("cpu",))
        with pytest.raises(ValueError):
            serving.export_generator(model, params, "/nonexistent",
                                     **{**kw, **bad})


# ---- (f) the other served programs are the parent's ---------------------

def _program_text(path):
    """A serialized program's module text without its debug locations
    (they hold the checkout's paths and line numbers)."""
    from jax import export as jax_export
    with open(path, "rb") as f:
        text = jax_export.deserialize(f.read()).mlir_module()
    out, i = [], 0
    while True:
        j = text.find("loc(", i)
        if j < 0:
            out.append(text[i:])
            break
        out.append(text[i:j])
        depth, k = 1, j + 4
        while depth:
            depth += {"(": 1, ")": -1}.get(text[k], 0)
            k += 1
        i = k
    lines = [ln.rstrip() for ln in "".join(out).splitlines()]
    return "\n".join(ln for ln in lines
                     if ln and not ln.startswith("#loc"))


#: sha256 (16 hex digits) of each program exported at the parent commit
#: (7da433e), weights baked: the same bytes for the same inputs. GPT's
#: three one-token programs are PR 36's: they return the greedy ids
#: beside the logits (its monolithic and verify programs are 7da433e's)
PARENT_PROGRAMS = {
    "gpt/decode.stablehlo": "c11f5b26cc8d1ff5",
    "gpt/model.stablehlo": "346d44b26d88bee3",
    "gpt/prefill.stablehlo": "73d6e69db488be7b",
    "gpt/prefill_chunk.stablehlo": "95ce124047bddaa8",
    "gpt/verify.stablehlo": "f232c29410b90020",
    "sdar/block_step.stablehlo": "49e36af09163f155",
    "sdar/prefill.stablehlo": "0deeb66418599bf8",
}


@pytest.mark.parametrize("name", ["gpt", "sdar"])
def test_gpt_and_sdar_programs_are_the_parents(name, tmp_path):
    """What this PR added to the block description, the expert layer and
    the exporter leaves the other two served models' programs as they
    were: the exported modules (weights baked from a fixed key) hash to
    what they hashed to at the parent commit."""
    import hashlib
    out = str(tmp_path / name)
    if name == "gpt":
        model = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
        serving.export_generator(
            model, model.init(jax.random.key(0)), out, prompt_len=32,
            max_new_tokens=8, ragged=True, stepwise=True, paged=True,
            slots=4, block_size=16, spec_tokens=2, prefill_chunk=16,
            platforms=("cpu",))
    else:
        model = get_model("sdar_moe_tiny", TrainConfig(
            model="sdar_moe_tiny", dtype="float32", param_dtype="float32"))
        serving.export_generator(
            model, model.init(jax.random.key(0)), out, prompt_len=32,
            max_new_tokens=16, ragged=True, stepwise=True, paged=True,
            slots=4, block_size=16, platforms=("cpu",))
    got = {f"{name}/{f}": hashlib.sha256(_program_text(
               os.path.join(out, f)).encode()).hexdigest()[:16]
           for f in sorted(os.listdir(out)) if f.endswith(".stablehlo")}
    # PR 46: a block artifact holds its prefill again at each narrower
    # width (here 16); what the parent exported is still what it was
    assert [k for k in got if k not in PARENT_PROGRAMS] == (
        ["sdar/prefill_16.stablehlo"] if name == "sdar" else [])
    assert {k: v for k, v in got.items() if k in PARENT_PROGRAMS} == {
        k: v for k, v in PARENT_PROGRAMS.items()
        if k.startswith(name + "/")}

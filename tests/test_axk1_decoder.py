"""Dense latent attention in every layer (``models/decoder.py``, a latent
without ``linear_attn`` or ``layer_types``: A.X-K1's layers at test
widths) against its plain reference (``benchmark/reference/a.x-k1.py``),
seeded random weights: how the kind follows from the description, YaRN's
table and ``mscale^2`` against hand values, the router against a
written-out loop and ``groups = 1`` against the plain top-k bit for bit,
the sixteen shares of an expert layer, chunks then steps through the pool
against the reference's one full forward (prompts that cross a chunk of
32 and a block of 16), the absorbed step against the expanded chunk on
the same rows, the chunk kernel interpreted against its XLA form, each
planted fault, block reuse, the artifact (a pool alone) and the engine."""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import planted_mla, weights_by_range        # noqa: E402
from benchmark.manifest import load_module                 # noqa: E402
from distributed_tensorflow_example_tpu import serving     # noqa: E402
from distributed_tensorflow_example_tpu.config import TrainConfig  # noqa: E402
from distributed_tensorflow_example_tpu.models import get_model   # noqa: E402
from distributed_tensorflow_example_tpu.models.decoder import (   # noqa: E402
    BlockDecoder, DecoderBlockConfig)
from distributed_tensorflow_example_tpu.ops import gqa, mla  # noqa: E402
from distributed_tensorflow_example_tpu.ops.moe import (    # noqa: E402
    moe_dropless, sigmoid_top_k)
from distributed_tensorflow_example_tpu.serving_batch import (  # noqa: E402
    GenerationEngine)

ref = load_module(os.path.join(ROOT, "benchmark", "reference", "a.x-k1.py"))
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                     "a.x-k1.json")))
CFG = CONFIG["rehearsal"]["sizes"]
SLOTS, BS, CHUNK, PROMPT, NEW = 3, 16, 32, 96, 24
NB = 8                                      # blocks a slot


def build(dtype: str, seed: int = 7):
    model = get_model("axk1_tiny", TrainConfig(
        model="axk1_tiny", dtype=dtype, param_dtype=dtype))
    for k, v in CONFIG["rehearsal"]["model_cfg"].items():
        setattr(model.cfg, k, v)
    params = weights_by_range.make_params(ref.param_spec(CFG), seed, dtype)
    return model, params


@pytest.fixture(scope="module")
def f32():
    return build("float32")


# ---- (a) the description ------------------------------------------------

def test_registry_builds_the_layers_from_a_description():
    big = get_model("axk1", TrainConfig(model="axk1", num_layers=5))
    c = big.cfg
    assert big.name == "axk1" and c.dense_latent and c.stateful
    assert not c.selecting_latent
    assert [c.mixer(i) for i in range(5)] == ["mla_dense"] * 5
    for key, attr in (("hidden_size", "hidden"),
                      ("num_attention_heads", "heads"),
                      ("q_lora_rank", "q_lora_rank"),
                      ("kv_lora_rank", "kv_lora_rank"),
                      ("qk_nope_head_dim", "qk_nope_dim"),
                      ("qk_rope_head_dim", "qk_rope_dim"),
                      ("v_head_dim", "v_head_dim"),
                      ("moe_intermediate_size", "expert_width"),
                      ("intermediate_size", "dense_width"),
                      ("num_experts_per_tok", "experts_per_token"),
                      ("routed_scaling_factor", "routed_scale"),
                      ("n_group", "expert_groups"),
                      ("topk_group", "top_expert_groups"),
                      ("first_k_dense_replace", "dense_layers"),
                      ("n_shared_experts", "shared_experts"),
                      ("rope_theta", "rope_theta"),
                      ("rms_norm_eps", "norm_eps"),
                      ("max_position_embeddings", "max_len")):
        assert getattr(c, attr) == CONFIG[key], key
    rs = CONFIG["rope_scaling"]
    assert c.rope_scaling == (rs["factor"], rs[
        "original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"], 1.0)
    assert c.rope_mscale_all_dim == rs["mscale_all_dim"]
    assert c.experts == CONFIG["published"]["n_routed_experts"] == 192
    assert c.vocab_size == CONFIG["published"]["vocab_size"]
    assert c.router_scores == "sigmoid" and c.mla_rope
    assert not (c.head_gate or c.lora_rescale or c.qk_norm)
    g = c.geometry("mla_dense")
    assert (g.heads, g.q_rank, g.rank, g.nope, g.pe, g.v, g.row) == (
        64, 1536, 512, 128, 64, 128, 640)
    # the engine's whole state is ONE array: the latent pool
    specs = big.state_specs(slots=24, num_blocks=5569, block_size=128)
    assert {k: (v["shape"], v["per"]) for k, v in specs.items()} == {
        "cache_latent": ([5, 5569, 128, 640], "block")}
    # the weights of the cell's share, counted from the spec: 6.98 GB
    n = sum(int(np.prod(shape)) for shape, _ in ref.param_spec(
        CONFIG).values())
    assert abs(2 * n / 1e9 - 6.98) < 0.01
    assert abs(2 * n / 1e9 - CONFIG["bytes"]["weights_gb"]) < 0.005
    # the program's leaves are the reference's
    big.cfg.experts_held, big.cfg.vocab_held = 12, 20480
    tree = jax.eval_shape(big.init, jax.random.key(0))
    got = {"/".join(str(k.key) for k in path): tuple(x.shape)
           for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == {k: tuple(s) for k, (s, _) in ref.param_spec(
        CONFIG).items()}


def test_the_kind_follows_from_the_description_not_a_name():
    """A latent alone at one token a step is dense latent attention;
    beside ``linear_attn`` it is Kimi's ``mla`` on the named layers,
    under ``layer_types`` and an indexer dots3's selecting kinds,
    without a latent Laguna's grouped-query kinds; a block-diffusion
    description stays ``gqa`` whatever its (unused) latent sizes."""
    tiny = DecoderBlockConfig.axk1_tiny()
    assert tiny.dense_latent and tiny.mixer(0) == tiny.mixer(4) == "mla_dense"
    assert DecoderBlockConfig.kimi_linear_tiny().mixer(3) == "mla"
    assert not DecoderBlockConfig.kimi_linear_tiny().dense_latent
    assert DecoderBlockConfig.dots3_note_tiny().mixer(0) == "mla_sparse"
    assert DecoderBlockConfig.dots3_note_tiny().selecting_latent
    assert DecoderBlockConfig.laguna_tiny().mixer(0) == "gqa_full"
    assert not DecoderBlockConfig.laguna_tiny().selecting_latent
    assert DecoderBlockConfig.tiny().mixer(0) == "gqa"
    assert not DecoderBlockConfig.tiny().stateful


@pytest.mark.parametrize("change,match", [
    (dict(q_lora_rank=0), "low-rank"),
    (dict(rope_scaling=(2.0, 32)), "rope_scaling"),
    (dict(expert_groups=3), "whole groups"),
    (dict(top_expert_groups=5), "group limit"),
    (dict(router_scores="softmax"), "sigmoid router"),
])
def test_what_the_description_cannot_say_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        BlockDecoder(dataclasses.replace(DecoderBlockConfig.axk1_tiny(),
                                         **change))


# ---- (b) YaRN on a latent row: the table and the scale -------------------

def test_yarn_table_and_mscale_are_the_hand_values():
    """The published sizes by hand: 32 pairs over 64 rope values at base
    1e4; pairs 0-9 turn more than 32 times in 4,096 positions and keep
    their frequency, pairs 23-31 turn less than once and are divided by
    32, a linear ramp between; ``mscale(32, 1) = 0.1 ln 32 + 1``; cos and
    sin times 1; the softmax scale ``192^-1/2 x 1.81326``."""
    got = ref.inverse_frequencies(CONFIG)
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 32, rtol=1e-6)
    r = (16 - 10) / (23 - 10)               # pair 16 on the ramp
    np.testing.assert_allclose(got[16], plain[16] / 32 * r
                               + plain[16] * (1 - r), rtol=1e-6)
    rs = CONFIG["rope_scaling"]
    np.testing.assert_allclose(got, gqa.yarn_inv_freq(
        64, 1e4, rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"]), rtol=1e-6)
    m = 0.1 * math.log(32) + 1
    assert abs(m - 1.34657) < 1e-5 and abs(m * m - 1.81326) < 1e-5
    assert ref.mscale(32, 1) == m and ref.rope_magnitude(CONFIG) == 1.0
    assert abs(ref.softmax_scale(CONFIG) - 192 ** -0.5 * 1.81326) < 1e-6
    g = DecoderBlockConfig.a_x_k1().geometry("mla_dense")
    assert abs(g.scale - ref.softmax_scale(CONFIG)) < 1e-9
    assert g.yarn == DecoderBlockConfig.a_x_k1().rope_scaling
    # the kinds the parent had keep the plain scale, to the bit
    for c, kind in ((DecoderBlockConfig.dots3_note_prev(), "mla_sparse"),
                    (DecoderBlockConfig.dots3_note_prev(), "mla_window"),
                    (DecoderBlockConfig.kimi_linear_48b_a3b(), "mla")):
        g = c.geometry(kind)
        assert g.scale == (g.nope + g.pe) ** -0.5 and g.yarn == ()
    # without mscale_all_dim the table stays and the scale is plain
    bare = dataclasses.replace(DecoderBlockConfig.a_x_k1(),
                               rope_mscale_all_dim=0.0)
    assert bare.geometry("mla_dense").scale == 192 ** -0.5


def test_the_program_rotates_at_the_references_frequencies(f32):
    """``_typed_q`` / ``_typed_latent`` of the tiny model against the
    reference's ``rope`` at the reference's table: the rope values of q
    (every head) and the one k_pe a token, before the row is cached."""
    model, params = f32
    g = model.cfg.geometry("mla_dense")
    mp = params["layers"]["2"]["mla"]
    rs = np.random.RandomState(0)
    n = jnp.asarray(rs.randn(40, 64), jnp.float32)
    pos = jnp.asarray(rs.permutation(200)[:40], jnp.int32)
    inv = ref.inverse_frequencies(CFG)
    assert inv.shape == (4,) and inv[0] == 1.0      # pair 0 keeps, 1.. / 8
    np.testing.assert_allclose(inv[1:], 1e4 ** (-np.arange(2, 8, 2) / 8)
                               / 8, rtol=1e-6)
    _, q = model._typed_q(mp, n, pos, g)
    c_q = ref.rms(n @ mp["wqa"], mp["q_norm"], 1e-6)
    want = (c_q @ mp["wqb"]).reshape(40, 4, 24)
    np.testing.assert_allclose(q[..., :16], want[..., :16], atol=2e-5)
    for h in range(4):
        np.testing.assert_allclose(
            q[:, h, 16:], ref.rope(want[:, h, 16:], pos, inv), atol=2e-5)
    row = model._typed_latent(mp, n, pos, g)
    assert row.shape == (40, g.row) and not np.asarray(row[:, 40:]).any()
    c = n @ mp["wkva"]
    np.testing.assert_allclose(row[:, :32], ref.rms(
        c[:, :32], mp["kv_norm"], 1e-6), atol=2e-5)
    np.testing.assert_allclose(row[:, 32:40], ref.rope(c[:, 32:], pos, inv),
                               atol=2e-5)


# ---- (c) the router: groups, and the default that is the parent's -------

def _loop_router(logits, bias, top_k, scale, groups, top_groups):
    """The choice written out row by row in numpy."""
    p = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    biased = p + np.asarray(bias, np.float64)
    t, e = p.shape
    size = e // groups
    idx, w = [], []
    for r in range(t):
        score = [np.sort(biased[r, j * size:(j + 1) * size])[-2:].sum()
                 for j in range(groups)]
        kept = np.argsort(score)[::-1][:top_groups]
        cand = [j for j in range(e) if j // size in kept]
        best = sorted(cand, key=lambda j: -biased[r, j])[:top_k]
        idx.append(best)
        w.append(scale * p[r, best] / p[r, best].sum())
    return np.asarray(idx), np.asarray(w)


@pytest.mark.parametrize("e,groups,top_groups,k", [(192, 8, 4, 8),
                                                   (16, 4, 2, 4),
                                                   (16, 1, 1, 4)])
def test_group_limited_choice_is_the_written_out_loop(e, groups,
                                                      top_groups, k):
    rs = np.random.RandomState(e + groups)
    logits = jnp.asarray(rs.randn(33, e) * 2, jnp.float32)
    bias = jnp.asarray(rs.randn(e) * 0.02, jnp.float32)
    w, idx = sigmoid_top_k(logits, bias, k, 2.5, groups, top_groups)
    widx, ww = _loop_router(logits, bias, k, 2.5, groups, top_groups)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(widx, -1))
    order = np.argsort(np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1),
        np.take_along_axis(ww, np.argsort(widx, -1), -1), rtol=2e-6)
    # every pick lies in one of the kept groups, at most top_groups of them
    used = np.asarray(idx) // (e // groups)
    assert max(len(set(r)) for r in used) <= top_groups
    # and the reference picks the same (its own code, group by group)
    z = dict(groups=groups, top_groups=top_groups, e=e, k=k, scale=2.5)
    x = jnp.asarray(rs.randn(33, 16), jnp.float32)
    router = jnp.asarray(rs.randn(16, e), jnp.float32)
    ridx, rw = ref.pick(z, {"router": router, "router_bias": bias}, x, "f32")
    w2, idx2 = sigmoid_top_k(jnp.dot(x, router, precision="highest"), bias,
                             k, 2.5, groups, top_groups)
    np.testing.assert_array_equal(np.asarray(ridx), np.asarray(idx2))
    np.testing.assert_allclose(rw, w2, rtol=2e-6)


def test_one_group_is_the_parents_router_bit_for_bit():
    """``groups = top_groups = 1`` (the default) traces what
    ``sigmoid_top_k`` and ``moe_dropless`` always traced, and gives the
    same bits: the other decoders' programs do not move."""
    rs = np.random.RandomState(3)
    logits = jnp.asarray(rs.randn(20, 16), jnp.float32)
    bias = jnp.asarray(rs.randn(16) * 0.02, jnp.float32)

    def parent(logits, bias, top_k, scale):     # PR 43's function, verbatim
        s = jax.nn.sigmoid(logits.astype(jnp.float32))
        pick = s + bias.astype(jnp.float32)
        _, idx = jax.lax.top_k(pick, top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        return w * (scale / jnp.sum(w, axis=-1, keepdims=True)), idx

    for a, b in zip(parent(logits, bias, 4, 2.5),
                    sigmoid_top_k(logits, bias, 4, 2.5, 1, 1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert str(jax.make_jaxpr(lambda x: parent(x, bias, 4, 2.5))(logits)) \
        == str(jax.make_jaxpr(lambda x: sigmoid_top_k(x, bias, 4, 2.5))(
            logits))
    x = jnp.asarray(rs.randn(12, 16), jnp.float32)
    router = jnp.asarray(rs.randn(16, 16), jnp.float32)
    ex = {n: jnp.asarray(rs.randn(4, *s) * 0.3, jnp.float32)
          for n, s in (("gate", (16, 8)), ("up", (16, 8)),
                       ("down", (8, 16)))}
    kw = dict(top_k=4, dtype=jnp.float32, scores="sigmoid",
              select_bias=bias, scale=2.5)
    a = jax.make_jaxpr(lambda x: moe_dropless(x, router, ex, **kw))(x)
    b = jax.make_jaxpr(lambda x: moe_dropless(
        x, router, ex, groups=1, top_groups=1, count_routed=None, **kw))(x)
    assert str(a) == str(b)
    # the count is a third result, asked for: rows with a held pick
    mask = jnp.arange(12) < 9               # three rows do not count
    y, rows, routed = moe_dropless(x, router, ex, groups=4, top_groups=2,
                                   count_routed=mask, **kw)
    _, idx = sigmoid_top_k(jnp.dot(x, router, precision="highest"), bias, 4,
                           2.5, 4, 2)
    assert int(routed) == int(np.sum(np.any(np.asarray(idx)[:9] < 4,
                                            axis=-1)))
    assert int(rows.sum()) == int(np.sum(np.asarray(idx) < 4))


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The deployment of the cell at test widths: sixteen chips hold one
    expert each of the 16 (the cell's hold 12 of 192), the router, its
    groups and the shared expert computed alike on all; the sixteen
    parts and ONE shared expert add up to the uncut reference's layer."""
    rs = np.random.RandomState(5)
    cfg = {**CFG, "n_routed_experts": 16,
           "published": {"n_routed_experts": 16, "vocab_size": 512}}
    z = ref._sizes(cfg)
    x = jnp.asarray(rs.randn(24, 64), jnp.float32)
    whole = {n: jnp.asarray(rs.randn(16, *s) * 0.2, jnp.float32)
             for n, s in (("gate", (64, 32)), ("up", (64, 32)),
                          ("down", (32, 64)))}
    shared = {n: jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)
              for n, s in (("gate", (64, 32)), ("up", (64, 32)),
                           ("down", (32, 64)))}
    router = jnp.asarray(rs.randn(64, 16), jnp.float32)
    bias = jnp.asarray(rs.randn(16) * 0.02, jnp.float32)
    xn = ref.rms(x, jnp.ones((64,)), 1e-6)
    want = ref.routed(z, {**whole, "router": router, "router_bias": bias},
                      xn, "f32") + ref.gated(shared, xn, "f32")
    model = BlockDecoder(dataclasses.replace(
        DecoderBlockConfig.axk1_tiny(), experts_held=1),
        dtype=jnp.float32, param_dtype=jnp.float32)
    total, routed_rows = 0.0, []
    for first in range(16):
        model.cfg.first_expert = first
        lp = {"ffn_norm": jnp.ones((64,)), "moe": {
            "router": router, "router_bias": bias,
            **{n: w[first:first + 1] for n, w in whole.items()}}}
        count = []
        y, rows = model._ffn_of(1, lp, x, count, jnp.ones((24,), bool))
        total = total + (y - x)
        routed_rows.append(int(count[0]))
        assert int(rows.sum()) == routed_rows[-1]   # one expert: its rows
    np.testing.assert_allclose(total + ref.gated(shared, xn, "f32"), want,
                               rtol=2e-5, atol=2e-5)
    # every row picks 4 experts: the shares' rows add up to 4 a row
    assert sum(routed_rows) == 4 * 24


# ---- (d) the two attention forms on the same rows ------------------------

def _latent_case(h=4, rank=32, nope=16, pe=8, v=16, t=96, nb=6, bs=16,
                 seed=1, dtype=jnp.float32, row=128):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(t, h, nope + pe), jnp.float32)
    lat = jnp.asarray(rs.randn(t, rank + pe), jnp.float32)
    w = jnp.asarray(rs.randn(rank, h, nope + v) * 0.3, jnp.float32)
    table = np.asarray(rs.permutation(np.arange(1, 1 + nb)), np.int32)
    rows = jnp.pad(lat, ((0, 0), (0, row - rank - pe)))
    pool = jnp.asarray(rs.randn(1 + nb, bs, row) * 50.0, jnp.float32)
    pool = pool.at[table[:t // bs]].set(rows.reshape(t // bs, bs, row))
    return q, lat, w, table, pool.astype(dtype)


def _plain(q, lat, w, rank, nope, scale):
    """Expanded causal attention a head at a time over every row."""
    t, h, _ = q.shape
    kv = jnp.einsum("sc,chd->shd", lat[:, :rank], w, precision="highest")
    pos = np.arange(t)
    ok = jnp.asarray(pos[None] <= pos[:, None])
    out = []
    for j in range(h):
        s = (jnp.einsum("qd,kd->qk", q[:, j, :nope], kv[:, j, :nope],
                        precision="highest")
             + jnp.einsum("qd,kd->qk", q[:, j, nope:], lat[:, rank:],
                          precision="highest")) * scale
        p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        out.append(jnp.einsum("qk,kd->qd", p, kv[:, j, nope:],
                              precision="highest"))
    return jnp.stack(out, axis=1)


def test_absorbed_step_and_expanded_chunk_agree_on_the_same_rows():
    """One mathematics in two forms: a chunk of 32 rows at start 32
    (expanded: K and V from the latent rows) and the same rows one a
    slot (absorbed: ``W_kvb`` in the query and on the way out), both
    against plain attention a head at a time, through a shuffled block
    table whose other blocks hold garbage."""
    rank, nope, pe, v = 32, 16, 8, 16
    scale = (nope + pe) ** -0.5 * 1.3
    q, lat, w, table, pool = _latent_case()
    want = _plain(q, lat, w, rank, nope, scale)
    kw = dict(rank=rank, nope=nope, pe=pe, v_dim=v, scale=scale)
    chunk = mla.mla_chunk_attention(q[32:64], pool, table, 32, w, **kw)
    np.testing.assert_allclose(chunk, want[32:64], rtol=2e-5, atol=2e-5)
    model = BlockDecoder(DecoderBlockConfig.axk1_tiny(), dtype=jnp.float32,
                         param_dtype=jnp.float32)
    g = dataclasses.replace(model.cfg.geometry("mla_dense"), mscale=1.0)
    at = np.arange(32, 64, dtype=np.int32)
    q_abs, w_vb = model._absorb({"wkvb": w.reshape(rank, -1)},
                                q[at] * (scale / g.scale), g)
    ctx = mla.mla_decode_attention(
        q_abs, pool, block_tables=np.stack([table] * 32), last=at, rank=rank)
    step = model._unabsorb(ctx, w_vb)
    np.testing.assert_allclose(step, want[32:64], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(step, chunk, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("start,tile", [(0, 256), (256, 256), (768, 256),
                                        (512, 128)])
def test_chunk_kernel_is_the_tile_loop(start, tile, dtype):
    """``mla_chunk_attn`` (interpreted here) against the XLA tile loop,
    through a shuffled block table: a chunk of 256 rows at its first,
    second and fourth position (tiles before the chunk unmasked, its own
    masked from positions, tiles past it neither fetched nor computed),
    and key tiles of half the chunk."""
    rs = np.random.RandomState(start + tile)
    t, h, rank, nope, pe, v, bs, nbp = 256, 3, 128, 128, 64, 128, 128, 8
    table = rs.permutation(np.arange(1, 1 + nbp)).astype(np.int32)
    pool = jnp.asarray(rs.randn(1 + nbp, bs, 256) * 0.5, dtype)
    q = jnp.asarray(rs.randn(t, h, nope + pe), jnp.float32)
    w = jnp.asarray(rs.randn(rank, h, nope + v) * 0.1, dtype)
    assert mla.chunk_tile_friendly(t, bs, rank, nope, pe, v, nbp, tile)
    kw = dict(rank=rank, nope=nope, pe=pe, v_dim=v, scale=0.1,
              key_tile=tile)
    want = mla.mla_chunk_attention(q, pool, table, start, w, impl="xla", **kw)
    got = mla.mla_chunk_attention(q, pool, table, start, w, impl="pallas",
                                  **kw)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    with pytest.raises(ValueError, match="whole 64-row tiles"):
        mla.mla_chunk_attention(q[:64], pool, table, 0, w, impl="pallas",
                                **kw)


def test_the_step_kernel_takes_64_heads_and_says_what_it_ran():
    """``paged_latent_attn`` (interpreted) at the cell's head count over
    a table of 16 blocks, against the gather; ``decode_schedule`` says
    which of the two ``mla_decode_attention`` runs for the same shapes
    (what the exporter keeps), and is held to what the call traces."""
    rs = np.random.RandomState(0)
    b, h, rank, row, bs, nb, n = 3, 64, 128, 256, 128, 16, 60
    pool = jnp.asarray(rs.randn(n, bs, row) * 0.3, jnp.float32)
    bt = np.stack([rs.permutation(np.arange(1, n))[:nb]
                   for _ in range(b)]).astype(np.int32)
    last = np.array([5, 700, 2047], np.int32)
    q = jnp.asarray(rs.randn(b, h, row) * 0.3, jnp.float32)
    outs = {}
    for impl in ("pallas", "xla", "auto"):
        fn = lambda q, impl=impl: mla.mla_decode_attention(  # noqa: E731
            q, pool, block_tables=bt, last=last, rank=rank, impl=impl)
        outs[impl] = fn(q)
        said = mla.decode_schedule(b, h, rank, bs, nb, impl)
        assert ("pallas_call" in str(jax.make_jaxpr(fn)(q))) == (
            said["kernel"] == "paged_latent_attn"), impl
    np.testing.assert_allclose(outs["pallas"], outs["xla"], rtol=2e-5,
                               atol=2e-5)
    assert mla.decode_schedule(b, h, rank, bs, nb, "pallas") == {
        "kernel": "paged_latent_attn", "blocks_per_step": 8,
        "grid": [3, 2], "heads": 64}
    assert mla.decode_schedule(b, h, rank, bs, nb, "auto") == {
        "kernel": "xla", "heads": 64}              # off the TPU
    assert mla.decode_schedule(b, h, rank, 16, nb, "pallas")[
        "kernel"] == "xla"                         # blocks of 16: no kernel


# ---- (e) chunked prefill, then decode, through the pool ------------------

def _state(model, fill=0.0):
    specs = model.state_specs(slots=SLOTS, num_blocks=1 + SLOTS * NB,
                              block_size=BS)
    return {k: jnp.full(v["shape"], fill, v["dtype"])
            for k, v in specs.items()}


def _serve_by_hand(model, params, toks, p, slot=1, state=None,
                   attention="xla"):
    """Chunked prefill of ``toks[:p]`` then one decode step a further
    token, through the state: logits at every position, the routing
    counts of every program, and the state."""
    state = _state(model) if state is None else state
    table = np.zeros((SLOTS, NB), np.int32)
    table[slot] = 1 + slot * NB + np.arange(NB)
    fn = jax.jit(lambda st, ids, n, start, cb: model.prefill_chunk(
        params, st, ids, n, start, slot, table[slot], cb, with_logits=True,
        attention=attention))
    rows, routed = [], []
    for start in range(0, p, CHUNK):
        n = min(CHUNK, p - start)
        ids = np.zeros((1, CHUNK), np.int32)
        ids[0, :n] = toks[start:start + n]
        cb = np.zeros((CHUNK // BS,), np.int32)
        need = -(-p // BS)
        for j in range(CHUNK // BS):
            if start // BS + j < need:
                cb[j] = table[slot, start // BS + j]
        out = fn(state, ids, n, start, cb)
        state = {k: out[k] for k in state}
        rows.append(np.asarray(out["logits"], np.float32)[:n])
        routed.append((int(out["expert_rows"]), int(out["routed_rows"])))
    first = int(out["ids"][0])
    step = jax.jit(lambda st, tok, pos, alive: model.decode_step(
        params, st, table, tok, pos, alive, attention=attention,
        with_logits=True))
    for t in range(p, len(toks)):
        tok, pos, alive = (np.zeros(SLOTS, np.int32) for _ in range(3))
        tok[slot], pos[slot], alive[slot] = toks[t], t, 1
        out = step(state, tok, pos, alive)
        state = {k: out[k] for k in state}
        rows.append(np.asarray(out["logits"], np.float32)[slot][None])
        routed.append((int(out["expert_rows"]), int(out["routed_rows"])))
    return np.concatenate(rows), first, state, routed


@pytest.mark.parametrize("p", [70, 64, 5, 33])
def test_chunked_prefill_then_decode_is_the_reference_forward_f32(f32, p):
    """float32 program against the float32 reference's ONE full forward
    over 90 tokens, LOGITS to 1e-4: the prompt in chunks of 32 (ending
    inside a chunk, on a chunk boundary, inside the first, one row into a
    block), the rest one token a step (absorbed), every prompt but the
    shortest across blocks of 16 and chunk edges; the pool starts full of
    another request's rows (a row is written before it is read). 5e-6
    read: float32 rounding through five layers in another order."""
    model, params = f32
    toks = np.random.RandomState(p).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, first, state, routed = _serve_by_hand(
        model, params, toks, p, state=_state(model, fill=30.0))
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert np.abs(got - want).max() < 3e-5
    assert first == int(np.argmax(want[p - 1]))
    # the other slots' blocks were never written
    pool = np.asarray(state["cache_latent"])
    assert (pool[:, 1:1 + NB] == 30.0).all()
    assert (pool[:, 1 + 2 * NB:] == 30.0).all()
    # a chunk counts its prompt rows (padding left out), a step its live
    # slot (one here), over 4 expert layers: some picked a held expert
    chunks = -(-p // CHUNK)
    for j, (_, r) in enumerate(routed[:chunks]):
        assert 0 <= r <= 4 * min(CHUNK, p - j * CHUNK)
    assert all(0 <= r <= 4 for _, r in routed[chunks:])
    assert sum(r for _, r in routed) > 0


def test_chunked_prefill_then_decode_in_bfloat16_is_near_it():
    """bfloat16 storage and operands against the float32 reference on the
    same (bfloat16-rounded) weights: the served argmax lies under the
    reference's best logit by under 0.15 on average (logits spread ~2; at
    these widths one expert or group chosen otherwise moves a logit far,
    which is why the rehearsal runs in float32)."""
    model, params = build("bfloat16")
    toks = np.random.RandomState(3).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, _, _, _ = _serve_by_hand(model, params, toks, 70)
    gap = want.max(-1) - want[np.arange(90), got.argmax(-1)]
    assert gap.mean() < 0.15 and np.abs(got - want).mean() < 0.15


def test_the_kernels_interpreted_serve_the_same_logits(f32):
    """The same through ``attention="pallas"``: off the TPU the chunk's
    shapes (blocks of 16) are refused by the chunk kernel's predicate, so
    the test widens what it can: the step kernel is refused too (blocks
    of 16); both refusals name the shapes."""
    model, params = f32
    toks = np.random.RandomState(1).randint(0, 384, 40).astype(np.int32)
    with pytest.raises(ValueError, match="128-row blocks"):
        _serve_by_hand(model, params, toks, 33, attention="pallas")


@pytest.mark.parametrize("fault", sorted(planted_mla.FAULTS))
def test_each_planted_fault_shows_in_the_logits(f32, fault):
    """What ``benchmark/planted_mla.py`` plants in the cell, by hand at
    test widths: each moves the logits far beyond rounding (the stale row
    only in the absorbed steps: the chunks stay the reference's)."""
    model, params = f32
    toks = np.random.RandomState(9).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    broken, _ = build("float32")
    planted_mla.FAULTS[fault](model=broken)
    got, _, _, _ = _serve_by_hand(broken, params, toks, 70,
                                  state=_state(model, fill=0.5))
    assert np.abs(got[70:] - want[70:]).max() > 1e-2
    if fault == "absorbed_uses_stale_row":
        np.testing.assert_allclose(got[:70], want[:70], atol=1e-4)


# ---- (f) the artifact and the engine ------------------------------------

@pytest.fixture(scope="module")
def artifact(f32, tmp_path_factory):
    model, params = f32
    out = str(tmp_path_factory.mktemp("axk1_tiny"))
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


def test_artifact_round_trip_records_a_pool_alone(artifact, f32):
    model, _ = f32
    meta = json.load(open(os.path.join(artifact, "export.json")))
    sm = meta["stepwise"]
    assert meta["model"] == "axk1"
    assert not os.path.exists(os.path.join(artifact, "prefill.stablehlo"))
    assert not os.path.exists(os.path.join(artifact, "model.stablehlo"))
    assert sm["prefill_chunk"] == CHUNK and sm["paged"]
    st = sm["state"]
    assert st["mixers"] == ["mla_dense"] * 5
    assert st["ffns"] == ["dense", "moe", "moe", "moe", "moe"]
    assert (st["index_topk"], st["window"]) == (0, 0)
    assert (st["expert_groups"], st["top_expert_groups"]) == (4, 2)
    assert list(st["specs"]) == ["cache_latent"]
    assert st["specs"] == json.loads(json.dumps(model.state_specs(
        slots=SLOTS, num_blocks=sm["num_blocks"], block_size=BS)))
    assert sm["pool_shape"] == st["specs"]["cache_latent"]["shape"]
    # a block costs its latent rows of five layers (128 values a row here)
    assert sm["block_bytes"] == 5 * BS * 128 * 4
    assert sm["decode"]["attn_schedule"] == {"decode": {
        "kernel": "xla", "heads": 4}}
    assert st["moe_rows"] == {
        "prefill_chunk": {"pairs": 4 * CHUNK, "bound": 4 * CHUNK},
        "decode": {"pairs": 4 * SLOTS, "bound": 4 * SLOTS}}
    assert set(st["moe_tiles"]["decode"].values()) == {"xla"}
    sw = serving.load_stepwise(artifact)
    pool = sw.make_pool()
    assert {k: list(v.shape) for k, v in pool.items()} == {
        "cache_latent": st["specs"]["cache_latent"]["shape"]}
    # nothing a slot: no zeroing program, and asking for one says why
    with pytest.raises(ValueError, match="no per-slot state"):
        sw.zero_slot(pool, 1)
    with pytest.raises(ValueError, match="scheduler"):
        serving.load_servable(artifact)({"input_ids": np.zeros((1, 4))})


def test_engine_generates_what_the_reference_generates(artifact, f32):
    """Requests of unlike lengths (inside one chunk, over three, on a
    chunk boundary) through chunked prefill and the shared one-token
    step, more requests than slots so that slots and blocks are reused
    (nothing is zeroed: the state contract with a pool alone): each gives
    the reference's greedy tokens, as it does alone."""
    _, params = f32
    rs = np.random.RandomState(11)
    lens = [5, 70, 64, 33, 96, 17]
    prompts = [rs.randint(0, 384, n).tolist() for n in lens]
    new = [NEW, 9, 16, NEW, 12, 7]
    want = [simulate(params, p, k) for p, k in zip(prompts, new)]
    launched = []
    eng = GenerationEngine(serving.load_stepwise(artifact))
    launch = eng._launch
    eng._launch = lambda program, *a, **k: (launched.append(program),
                                            launch(program, *a, **k))[1]
    eng.start()
    try:
        assert eng.prefill_chunk_tokens == CHUNK
        assert eng.cache.prefix is None
        handles = [eng.submit(p, max_new=k) for p, k in zip(prompts, new)]
        got = [h.result(timeout=300) for h in handles]
        assert got == want
        st = eng.stats()
        assert st["admissions"] == 6 > SLOTS
        assert st["prefill_chunks"] == sum(-(-n // CHUNK) for n in lens)
        assert st["chunks_behind_step"] > 0
        assert set(launched) == {"prefill_chunk", "decode"}    # no zero_slot
        pool = int(np.prod(st["pool_shape"])) * 4
        assert st["state"]["bytes"] == {"cache_latent": pool}
        assert st["latent_pool_bytes"] == pool
        assert st["state_bytes"] == st["window_cache_bytes"] == 0
        assert st["kv_pool_bytes"] == st["index_pool_bytes"] == 0
        assert st["attn_schedule"]["decode"]["kernel"] == "xla"
        assert st["decode_logits_steps"] == 0
        # every dispatched row of every expert layer is counted; the rows
        # that picked one of the 4 held experts of 16 are fewer
        rows = st["moe_rows"] // 4
        assert 0 < st["moe_routed_rows"] < rows
        alone = eng.submit(prompts[1], max_new=new[1]).result(timeout=300)
        assert alone == want[1]
    finally:
        eng.close()


def test_a_request_through_the_http_server(artifact, f32):
    """End to end: ``PredictServer`` over the engine, ``:generate``."""
    import urllib.request
    from distributed_tensorflow_example_tpu.serving_http import (
        PredictServer)
    _, params = f32
    prompt = np.random.RandomState(2).randint(0, 384, 40).tolist()
    srv = PredictServer(artifact, port=0, prefix_cache=False)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            f"{base}/v1/models/{srv.name}:generate",
            data=json.dumps({"inputs": {"input_ids": [prompt]},
                             "max_new": 6}).encode(),
            headers={"Content-Type": "application/json"})
        body = json.load(urllib.request.urlopen(req, timeout=300))
        page = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=60).read().decode()
    finally:
        srv.stop(drain=False)
    assert body["generations"][0] == simulate(params, prompt, 6)
    for name in ("serving_moe_routed_rows_total", "serving_moe_rows_total",
                 "serving_latent_pool_bytes"):
        assert f"\n{name} " in page, name


def test_what_the_artifact_refuses_is_said(artifact):
    sw = serving.load_stepwise(artifact)
    with pytest.raises(ValueError, match="rewind"):
        GenerationEngine(sw, spec_tokens=2)
    with pytest.raises(ValueError, match="chunk"):
        GenerationEngine(sw, prefill_chunk_tokens=16)
    eng = GenerationEngine(sw, prefix_cache=True)
    assert eng.cache.prefix is None
    with pytest.raises(ValueError, match="greedy"):
        eng.submit([1, 2, 3], temperature=0.7)
    eng.close()


def test_spans_say_what_the_pool_was_read_for(artifact):
    """``prefill_chunk`` and ``decode_step`` spans of a dense-latent
    artifact carry ``kv_bytes`` (the contexts' latent rows as stored, all
    layers), ``context_rows``, ``expert_rows`` and ``routed_rows`` (of
    the last program of their kind the host has read)."""
    eng = GenerationEngine(serving.load_stepwise(artifact))
    token = 5 * 128 * 4                 # a token's rows of five layers
    chunk = {**eng._describe_selection(32 + 1 + np.arange(20), 52),
             **eng._describe_chunk_routing()}
    assert chunk == {"context_rows": 5 * sum(range(33, 53)),
                     "kv_bytes": 52 * token, "expert_rows": 0,
                     "routed_rows": 0}
    eng._routed_rows_last, eng._expert_rows_last = 7, 9
    feats = {"alive": np.array([1, 0, 1]), "pos": np.array([4, 0, 40]),
             "tok": np.zeros(3, np.int32)}
    step = eng._describe_state_decode(feats)
    assert step["slots"] == 2 and step["state_bytes"] == 0
    assert step["context_rows"] == 5 * (5 + 41)
    assert step["kv_bytes"] == (5 + 41) * token
    assert (step["expert_rows"], step["routed_rows"]) == (9, 7)
    assert step["host_bytes"] == 4 * SLOTS + 12
    assert "selected_rows" not in step and "window_bytes" not in step
    # the parents' artifacts say nothing of routed rows
    assert not eng._dsa and not eng._gqa and eng._dense_latent == 5
    eng.close()

"""Grouped-query attention in the one-token forwards (``models/decoder.py``,
``layer_types`` without an indexer: Laguna-S-2.1's layers at test widths)
against its plain reference (``benchmark/reference/laguna-s-2.1.py``),
seeded random weights: the two head counts and groups against a per-head
loop, YaRN's frequencies against the closed form, chunked prefill and
one-token decode through the K/V pool and the rings (prompts that cross a
chunk of 32, a block of 16 and the ring's wrap at 16), the kernels in
interpret mode against their XLA forms, the shares of an expert layer,
each planted fault, slot reuse, the artifact and the engine."""

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

from benchmark import planted_gqa, weights_by_range        # noqa: E402
from benchmark.manifest import load_module                 # noqa: E402
from distributed_tensorflow_example_tpu import serving     # noqa: E402
from distributed_tensorflow_example_tpu.config import TrainConfig  # noqa: E402
from distributed_tensorflow_example_tpu.models import get_model   # noqa: E402
from distributed_tensorflow_example_tpu.models.decoder import (   # noqa: E402
    BlockDecoder, DecoderBlockConfig, _rope)
from distributed_tensorflow_example_tpu.ops import gqa, mla  # noqa: E402
from distributed_tensorflow_example_tpu.ops.moe import moe_dropless  # noqa: E402
from distributed_tensorflow_example_tpu.serving_batch import (  # noqa: E402
    GenerationEngine)

ref = load_module(os.path.join(ROOT, "benchmark", "reference",
                               "laguna-s-2.1.py"))
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                     "laguna-s-2.1.json")))
CFG = CONFIG["rehearsal"]["sizes"]
SLOTS, BS, CHUNK, PROMPT, NEW = 3, 16, 32, 96, 24
NB = 8                                      # blocks a slot


def build(dtype: str, seed: int = 7):
    model = get_model("laguna_tiny", TrainConfig(
        model="laguna_tiny", dtype=dtype, param_dtype=dtype))
    for k, v in CONFIG["rehearsal"]["model_cfg"].items():
        setattr(model.cfg, k, v)
    params = weights_by_range.make_params(ref.param_spec(CFG), seed, dtype)
    return model, params


@pytest.fixture(scope="module")
def f32():
    return build("float32")


# ---- (a) the description ------------------------------------------------

def test_registry_builds_the_layers_from_a_description():
    big = get_model("laguna", TrainConfig(model="laguna", num_layers=9))
    c = big.cfg
    assert list(c.layer_types) == CONFIG["layer_types"]
    assert [c.mixer(i) for i in range(9)] == [
        "gqa_full", *["gqa_window"] * 3, "gqa_full", *["gqa_window"] * 3,
        "gqa_full"]
    assert [c.heads_of(c.mixer(i)) for i in range(9)] == CONFIG[
        "num_attention_heads_per_layer"][:9]
    assert (c.kv_heads, c.head_dim, c.window, c.ring) == (8, 128, 512, 512)
    rp = CONFIG["rope_parameters"]
    assert c.rotary_dim == 128 * rp["full_attention"]["partial_rotary_factor"]
    assert c.rope_scaling == tuple(rp["full_attention"][k] for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "attention_factor"))
    assert (c.rope_theta, c.swa_rope_theta) == (
        rp["full_attention"]["rope_theta"],
        rp["sliding_attention"]["rope_theta"])
    for key, attr in (("hidden_size", "hidden"),
                      ("moe_intermediate_size", "expert_width"),
                      ("intermediate_size", "dense_width"),
                      ("num_experts_per_tok", "experts_per_token"),
                      ("moe_routed_scaling_factor", "routed_scale"),
                      ("sliding_window", "window")):
        assert getattr(c, attr) == CONFIG[key], key
    assert c.experts == CONFIG["published"]["num_experts"]
    assert c.router_scores == "softmax" and c.head_gate and not c.qk_norm
    specs = big.state_specs(slots=24, num_blocks=3073, block_size=128)
    assert {k: (v["shape"], v["per"]) for k, v in specs.items()} == {
        "cache_k": ([3, 3073, 128, 1024], "block"),
        "cache_v": ([3, 3073, 128, 1024], "block"),
        "cache_window_k": ([6, 24, 512, 1024], "slot"),
        "cache_window_v": ([6, 24, 512, 1024], "slot")}
    # the weights of the cell's share, counted from the spec: 6.4 GB
    n = sum(int(np.prod(shape)) for shape, _ in ref.param_spec(
        CONFIG).values())
    assert abs(2 * n / 1e9 - CONFIG["bytes"]["weights_gb"]) < 0.005
    assert abs(2 * n / 1e9 - 6.40) < 0.05


@pytest.mark.parametrize("change,match", [
    (dict(block_length=4), "one token"),
    (dict(window=0), "needs window"),
    (dict(index_topk=8), "index_topk and index_heads"),
    (dict(index_topk=8, index_heads=2), "latent cache"),
    (dict(swa_heads=5), "do not divide"),
    (dict(rotary_dim=7), "rotary_dim"),
    (dict(rope_scaling=(2.0, 32)), "rope_scaling"),
])
def test_what_the_description_cannot_say_is_refused(change, match):
    """The construction-time refusals, narrowed to what is not built: a
    kind a layer under a block step, named layers without a window, half
    an indexer, a selection over K/V heads, heads that do not group."""
    with pytest.raises(ValueError, match=match):
        BlockDecoder(dataclasses.replace(DecoderBlockConfig.laguna_tiny(),
                                         **change))


def test_the_kind_follows_from_the_description_not_the_name():
    """``full_attention`` is latent attention under an indexer in dots3's
    description and grouped-query attention in Laguna's."""
    assert DecoderBlockConfig.dots3_note_tiny().mixer(0) == "mla_sparse"
    assert DecoderBlockConfig.dots3_note_tiny().mixer(2) == "mla_window"
    assert DecoderBlockConfig.laguna_tiny().mixer(0) == "gqa_full"
    assert DecoderBlockConfig.laguna_tiny().mixer(2) == "gqa_window"
    assert DecoderBlockConfig.kimi_linear_tiny().mixer(3) == "mla"
    assert DecoderBlockConfig.tiny().mixer(0) == "gqa"


# ---- (b) rotary positions -----------------------------------------------

@pytest.mark.parametrize("which", ["published", "tiny"])
def test_yarn_frequencies_are_the_closed_form(which):
    """``ops/gqa.yarn_inv_freq`` against the formula written out pair by
    pair: pairs that turn more than ``beta_fast`` times in the original
    context keep ``theta^(-2i / dim)``, pairs that turn fewer than
    ``beta_slow`` times are divided by ``factor``, a linear ramp between;
    and against the reference's own."""
    rp = (CONFIG if which == "published" else CFG)["rope_parameters"][
        "full_attention"]
    head_dim = 128 if which == "published" else 16
    dim = int(head_dim * rp["partial_rotary_factor"])
    got = gqa.yarn_inv_freq(dim, rp["rope_theta"], rp["factor"],
                            rp["original_max_position_embeddings"],
                            rp["beta_fast"], rp["beta_slow"])
    base, orig = rp["rope_theta"], rp["original_max_position_embeddings"]

    def pair(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(pair(rp["beta_fast"])), 0)
    high = min(math.ceil(pair(rp["beta_slow"])), dim - 1)
    want = []
    for i in range(dim // 2):
        f = base ** (-2 * i / dim)
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / rp["factor"] * r + f * (1 - r))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got, ref.inverse_frequencies(rp, head_dim),
                               rtol=1e-6)
    if which == "published":
        assert (low, high) == (9, 18)
        # the fast pairs untouched, the slow ones stretched 128 times
        assert got[0] == 1.0 and got[9] == np.float32(base ** (-18 / 64))
        np.testing.assert_allclose(got[18:], [base ** (-2 * i / 64) / 128
                                              for i in range(18, 32)],
                                   rtol=1e-6)


def test_rope_takes_a_width_and_a_table_and_defaults_to_the_parents():
    """``_rope`` over the leading ``width`` values with a table of
    frequencies and a factor on cos and sin; without them what it always
    traced (the other decoders' programs)."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(5, 3, 16), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 40, 900], jnp.int32)
    inv = jnp.asarray(rs.rand(4), jnp.float32)
    got = _rope(x, pos, 1e4, width=8, inv_freq=inv, factor=1.3)
    ang = np.asarray(pos, np.float32)[:, None] * np.asarray(inv)[None]
    cos, sin = np.cos(ang)[:, None] * 1.3, np.sin(ang)[:, None] * 1.3
    xn = np.asarray(x)
    want = xn.copy()
    want[..., :4] = xn[..., :4] * cos - xn[..., 4:8] * sin
    want[..., 4:8] = xn[..., 4:8] * cos + xn[..., :4] * sin
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]), xn[..., 8:])
    plain = jax.make_jaxpr(lambda x: _rope(x, pos, 1e4))(x)
    assert "concatenate" in str(plain) and str(plain).count("slice") == 2


# ---- (c) the two head counts, against a per-head loop --------------------

def _per_head(q, k, v, allowed, kvh):
    """Plain attention a query head at a time: head j reads KV head
    ``j // (H / KVH)``. q [T, H, D], k / v [S, KVH x D]."""
    t, h, d = q.shape
    k, v = k.reshape(-1, kvh, d), v.reshape(-1, kvh, d)
    out = []
    for j in range(h):
        at = j // (h // kvh)
        s = jnp.einsum("qd,kd->qk", q[:, j], k[:, at],
                       precision="highest") / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1)
        out.append(jnp.einsum("qk,kd->qd", p, v[:, at],
                              precision="highest"))
    return jnp.stack(out, axis=1)


def _kv_case(h, kvh=2, d=16, t=96, nb=6, bs=16, seed=1):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(t, h, d), jnp.float32)
    k = jnp.asarray(rs.randn(t, kvh * d), jnp.float32)
    v = jnp.asarray(rs.randn(t, kvh * d), jnp.float32)
    table = np.asarray(rs.permutation(np.arange(1, 1 + nb)), np.int32)
    pools = []
    for rows in (k, v):
        pool = jnp.asarray(rs.randn(1 + nb, bs, kvh * d), jnp.float32) * 50.0
        pools.append(pool.at[table[:t // bs]].set(
            rows.reshape(t // bs, bs, kvh * d)))
    return q, k, v, table, pools


@pytest.mark.parametrize("heads,group", [(12, 6), (18, 9), (4, 2)])
def test_full_layer_forwards_are_a_per_head_loop(heads, group):
    """A chunk of 32 rows at start 32 through a shuffled block table, and
    one row a slot, against plain causal attention a head at a time:
    groups of 6 and of 9 (the published two) and of 2; the pool's other
    blocks (garbage) are never read."""
    q, k, v, table, (kp, vp) = _kv_case(heads)
    assert heads // 2 == group
    pos = np.arange(96)
    want = _per_head(q, k, v, jnp.asarray(pos[None] <= pos[:, None]), 2)
    got = gqa.gqa_prefill_attention(q[32:64], kp, vp, table, 32,
                                    key_tile=16)
    np.testing.assert_allclose(got, want[32:64], rtol=2e-5, atol=2e-5)
    at = np.array([95, 40, 0], np.int32)
    step = gqa.paged_gqa_decode_attention(
        q[at], kp, vp, block_tables=np.stack([table] * 3), pos=at)
    np.testing.assert_allclose(step, want[at], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("heads,window", [(18, 9), (12, 17), (18, 16)])
def test_window_forwards_see_the_last_rows_only(heads, window):
    """A chunk against the rings the chunks before left, and one token
    over the rings, are plain attention under the band ``t - window < s
    <= t``, 9 and 6 query heads a KV head; rows older than the window,
    and a ring full of another request's rows, change nothing. The ring
    wraps at 16, 32 and 16 rows (a window of 16 fills its ring)."""
    q, k, v, _, _ = _kv_case(heads, seed=2)
    rows = mla.ring_rows(window)
    pos = np.arange(96)
    band = (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None] - window)
    want = np.asarray(_per_head(q, k, v, jnp.asarray(band), 2))
    rk = jnp.full((rows, k.shape[1]), 1e3, jnp.float32)     # stale rows
    rv = jnp.full((rows, k.shape[1]), 1e3, jnp.float32)
    got = []
    for start, n in ((0, 32), (32, 32), (64, 20)):
        sl = slice(start, start + 32)
        got.append(gqa.gqa_window_prefill_attention(
            q[sl], k[sl], v[sl], rk, rv, start, window=window,
            block_size=16)[:n])
        rk = mla.ring_after_chunk(rk, k[sl], start, n)
        rv = mla.ring_after_chunk(rv, v[sl], start, n)
    np.testing.assert_allclose(np.concatenate(got), want[:84], rtol=2e-5,
                               atol=2e-5)
    held = np.asarray(mla.ring_positions(83, rows))
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(k)[held])
    # one token a step from there, two slots (one of them a stale ring)
    rks = jnp.stack([rk, jnp.full_like(rk, 1e3)])
    rvs = jnp.stack([rv, jnp.full_like(rv, 1e3)])
    for t in range(84, 96):
        rks = rks.at[0, t % rows].set(k[t]).at[1, 0].set(k[0])
        rvs = rvs.at[0, t % rows].set(v[t]).at[1, 0].set(v[0])
        p2 = np.array([t, 0], np.int32)
        out = gqa.gqa_window_decode_attention(
            q[p2], rks, rvs, jnp.asarray(p2), window=window)
        np.testing.assert_allclose(out[0], want[t], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(out[1], want[0], rtol=2e-5, atol=2e-5)


# ---- (c') the kernels, interpreted, against their XLA forms --------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("heads", [12, 18])
def test_paged_decode_kernel_is_the_gather(heads, dtype):
    """``paged_gqa_attn`` (interpreted here) against the gathered run:
    rows at the first, a middle and the last position of an 8-block
    table, under 1, 4 and 8 entries a grid step, groups of 6 and 9."""
    rs = np.random.RandomState(heads)
    b, kvh, d, bs, nb, n = 3, 2, 128, 128, 8, 40
    kp = jnp.asarray(rs.randn(n, bs, kvh * d) * 0.5, dtype)
    vp = jnp.asarray(rs.randn(n, bs, kvh * d) * 0.5, dtype)
    bt = np.stack([rs.permutation(np.arange(1, n))[:nb]
                   for _ in range(b)]).astype(np.int32)
    pos = np.array([5, 300, 1023], np.int32)
    q = jnp.asarray(rs.randn(b, heads, d), dtype)
    want = gqa.paged_gqa_decode_attention(q, kp, vp, block_tables=bt,
                                          pos=pos, impl="xla")
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for entries in (1, 4, 8):
        got = gqa.paged_gqa_decode_attention(
            q, kp, vp, block_tables=bt, pos=pos, impl="pallas",
            entries=entries)
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert gqa.gqa_entries(128) == 4 and gqa.gqa_entries(6) == 3
    with pytest.raises(ValueError, match="block_size"):
        gqa.paged_gqa_decode_attention(q, kp[:, :16], vp[:, :16],
                                       block_tables=bt, pos=pos,
                                       impl="pallas")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("start", [0, 256, 768])
def test_chunk_kernel_is_the_tile_loop(start, dtype):
    """``gqa_chunk_attn`` (interpreted here) against the XLA tile loop,
    causal, through a shuffled block table: a chunk of 256 rows at its
    first, second and fourth position (tiles past the chunk's own are
    neither fetched nor computed)."""
    rs = np.random.RandomState(start)
    t, h, kvh, d, bs, nbp = 256, 6, 2, 128, 128, 8
    table = rs.permutation(np.arange(1, 1 + nbp)).astype(np.int32)
    kp = jnp.asarray(rs.randn(1 + nbp, bs, kvh * d) * 0.5, dtype)
    vp = jnp.asarray(rs.randn(1 + nbp, bs, kvh * d) * 0.5, dtype)
    q = jnp.asarray(rs.randn(t, h, d), jnp.float32)
    assert gqa.chunk_tile_friendly(t, bs, d, nbp, 256)
    want = gqa.gqa_prefill_attention(q, kp, vp, table, start, key_tile=256,
                                     impl="xla")
    got = gqa.gqa_prefill_attention(q, kp, vp, table, start, key_tile=256,
                                    impl="pallas")
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    with pytest.raises(ValueError, match="whole 256-row tiles"):
        gqa.gqa_prefill_attention(q[:64], kp, vp, table, 0, key_tile=256,
                                  impl="pallas")


@pytest.mark.parametrize("window,rows", [(200, 208), (256, 256), (512, 512)])
def test_window_chunk_kernel_is_the_tile_loop(window, rows):
    """The band under the same kernel: two chunks of 512 rows against the
    ring and themselves, a ring that is whole tiles (256, 512) and one
    that is not (208: one tile over the keys' width)."""
    rs = np.random.RandomState(window)
    h, kvh, d = 6, 2, 128
    k = jnp.asarray(rs.randn(1024, kvh * d) * 0.5, jnp.float32)
    v = jnp.asarray(rs.randn(1024, kvh * d) * 0.5, jnp.float32)
    q = jnp.asarray(rs.randn(1024, h, d), jnp.float32)
    outs = {}
    for impl in ("xla", "pallas"):
        rk = jnp.full((rows, kvh * d), 1e3, jnp.float32)
        rv = jnp.full((rows, kvh * d), 1e3, jnp.float32)
        got = []
        for start in (0, 512):
            sl = slice(start, start + 512)
            got.append(gqa.gqa_window_prefill_attention(
                q[sl], k[sl], v[sl], rk, rv, start, window=window,
                tile=256, impl=impl))
            rk = mla.ring_after_chunk(rk, k[sl], start, 512)
            rv = mla.ring_after_chunk(rv, v[sl], start, 512)
        outs[impl] = np.concatenate(got)
    np.testing.assert_allclose(outs["pallas"], outs["xla"], rtol=2e-5,
                               atol=2e-5)
    pos = np.arange(1024)
    band = (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None] - window)
    want = _per_head(q, k, v, jnp.asarray(band), 2)
    np.testing.assert_allclose(outs["xla"], want, rtol=2e-5, atol=2e-5)


# ---- (d) the router's scale, and the shares of a layer -------------------

def test_routed_scale_applies_on_the_softmax_path_and_1_traces_nothing():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(12, 16), jnp.float32)
    router = jnp.asarray(rs.randn(16, 8), jnp.float32)
    ex = {n: jnp.asarray(rs.randn(8, *s) * 0.3, jnp.float32)
          for n, s in (("gate", (16, 8)), ("up", (16, 8)),
                       ("down", (8, 16)))}
    kw = dict(top_k=3, dtype=jnp.float32)
    one, _ = moe_dropless(x, router, ex, **kw)
    scaled, _ = moe_dropless(x, router, ex, scale=2.5, **kw)
    np.testing.assert_allclose(scaled, 2.5 * one, rtol=1e-5, atol=1e-6)
    # scale 1 is the parent's program: no multiplication is traced
    a = str(jax.make_jaxpr(lambda x: moe_dropless(x, router, ex, **kw))(x))
    b = str(jax.make_jaxpr(lambda x: moe_dropless(
        x, router, ex, scale=1.0, **kw))(x))
    assert a == b


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(f32):
    """Four shares of the experts (2 of 8 each; the cell's chip holds 1
    in 8), the shared expert counted once, add up to the uncut
    reference's layer."""
    _, params = f32
    z = ref._sizes({**CFG, "num_experts": 8,
                    "published": {"num_experts": 8, "vocab_size": 512}})
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(20, 64), jnp.float32)
    whole = {n: jnp.asarray(rs.randn(8, *s) * 0.2, jnp.float32)
             for n, s in (("gate", (64, 32)), ("up", (64, 32)),
                          ("down", (32, 64)))}
    mp = {**whole, "router": params["layers"]["1"]["moe"]["router"],
          "shared": params["layers"]["1"]["moe"]["shared"]}
    want = ref.routed(z, mp, x, "f32") + ref.gated(mp["shared"], x, "f32")
    model = BlockDecoder(dataclasses.replace(
        DecoderBlockConfig.laguna_tiny(), experts_held=2),
        dtype=jnp.float32, param_dtype=jnp.float32)
    total = 0.0
    for first in (0, 2, 4, 6):
        model.cfg.first_expert = first
        lp = {"ffn_norm": jnp.ones((64,)), "moe": {
            "router": mp["router"],
            **{n: w[first:first + 2] for n, w in whole.items()}}}
        y, rows = model._ffn_of(1, lp, jnp.zeros_like(x) + x)
        total = total + (y - x)
        assert int(rows.sum()) > 0
    shared = ref.gated(mp["shared"], ref.rms(x, jnp.ones((64,)), 1e-6),
                       "f32")
    xn = ref.rms(x, jnp.ones((64,)), 1e-6)
    want = ref.routed(z, mp, xn, "f32") + shared
    np.testing.assert_allclose(total + shared, want, rtol=2e-5, atol=2e-5)


# ---- (e) chunked prefill, then decode, through pool and rings ------------

def _state(model, fill=0.0):
    specs = model.state_specs(slots=SLOTS, num_blocks=1 + SLOTS * NB,
                              block_size=BS)
    return {k: jnp.full(v["shape"], fill, v["dtype"])
            for k, v in specs.items()}


def _serve_by_hand(model, params, toks, p, slot=1, state=None,
                   attention="xla"):
    """Chunked prefill of ``toks[:p]`` then one decode step a further
    token, through the state: logits at every position, and the state."""
    state = _state(model) if state is None else state
    table = np.zeros((SLOTS, NB), np.int32)
    table[slot] = 1 + slot * NB + np.arange(NB)
    fn = jax.jit(lambda st, ids, n, start, cb: model.prefill_chunk(
        params, st, ids, n, start, slot, table[slot], cb, with_logits=True,
        attention=attention))
    rows = []
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
    return np.concatenate(rows), first, state


@pytest.mark.parametrize("p", [70, 64, 5, 33])
def test_chunked_prefill_then_decode_is_the_reference_forward_f32(f32, p):
    """float32 program against the float32 reference's one full forward
    over 90 tokens, LOGITS: the prompt in chunks of 32 (ending inside a
    chunk, on a chunk boundary, inside the first, one row into a block),
    the rest one token a step; every prompt but the shortest crosses the
    window (9), the ring's wrap (16) and a block (16). Tolerance 3e-5 on
    logits of magnitude ~2: float32 rounding through five layers in
    another order (1.5e-5 read)."""
    model, params = f32
    toks = np.random.RandomState(p).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, first, state = _serve_by_hand(model, params, toks, p)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert first == int(np.argmax(want[p - 1]))
    # the other slots' rings were never touched
    for name in ("cache_window_k", "cache_window_v"):
        assert not np.asarray(state[name][:, 0]).any()
        assert not np.asarray(state[name][:, 2]).any()


def test_chunked_prefill_then_decode_in_bfloat16_is_near_it():
    """bfloat16 storage and operands against the float32 reference on the
    same (bfloat16-rounded) weights: the served argmax lies under the
    reference's best logit by under 0.15 on average (logits spread ~2;
    at these widths one expert of 8 chosen otherwise moves a logit far,
    which is why the rehearsal runs in float32)."""
    model, params = build("bfloat16")
    toks = np.random.RandomState(3).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, _, _ = _serve_by_hand(model, params, toks, 70)
    gap = want.max(-1) - want[np.arange(90), got.argmax(-1)]
    assert gap.mean() < 0.15 and np.abs(got - want).mean() < 0.15


def test_a_reused_slot_and_reused_blocks_never_reach_the_next_request(f32):
    """A request that takes a slot whose rings, and blocks whose K and V
    rows, another request left (here: every array full of large values)
    is served the reference's logits all the same: a row of either cache
    is written before it is read."""
    model, params = f32
    toks = np.random.RandomState(13).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, _, _ = _serve_by_hand(model, params, toks, 70,
                               state=_state(model, fill=30.0))
    np.testing.assert_allclose(got, want, atol=3e-5)
    _, _, dirty = _serve_by_hand(model, params, toks[::-1].copy(), 80)
    got, _, _ = _serve_by_hand(model, params, toks, 70, state=dirty)
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("fault", sorted(planted_gqa.FAULTS))
def test_each_planted_fault_shows_in_the_logits(f32, fault):
    """What ``benchmark/planted_gqa.py`` plants in the cell, by hand at
    test widths: each moves the logits far beyond rounding."""
    model, params = f32
    toks = np.random.RandomState(9).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    broken, _ = build("float32")
    planted_gqa.FAULTS[fault](model=broken)
    got, _, _ = _serve_by_hand(broken, params, toks, 70,
                               state=_state(model, fill=0.5))
    assert np.abs(got[40:] - want[40:]).max() > 1e-2


# ---- (f) the artifact and the engine ------------------------------------

@pytest.fixture(scope="module")
def artifact(f32, tmp_path_factory):
    model, params = f32
    out = str(tmp_path_factory.mktemp("laguna_tiny"))
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


def test_artifact_round_trip_records_its_two_caches(artifact, f32):
    model, _ = f32
    meta = json.load(open(os.path.join(artifact, "export.json")))
    sm = meta["stepwise"]
    assert meta["model"] == "laguna"
    assert not os.path.exists(os.path.join(artifact, "prefill.stablehlo"))
    assert not os.path.exists(os.path.join(artifact, "model.stablehlo"))
    assert sm["prefill_chunk"] == CHUNK and sm["paged"]
    st = sm["state"]
    assert st["mixers"] == ["gqa_full", "gqa_window", "gqa_window",
                            "gqa_window", "gqa_full"]
    assert st["ffns"] == ["dense", "moe", "moe", "moe", "moe"]
    assert (st["index_topk"], st["window"]) == (0, 9)
    assert st["specs"] == json.loads(json.dumps(model.state_specs(
        slots=SLOTS, num_blocks=sm["num_blocks"], block_size=BS)))
    assert sm["pool_shape"] == st["specs"]["cache_k"]["shape"]
    # a block costs its K rows and its V rows, both full layers
    assert sm["block_bytes"] == 2 * 2 * BS * 32 * 4
    # the schedule each program's one-token attention was traced with,
    # the tiles and the rows of its expert layers
    assert sm["decode"]["attn_schedule"] == {"decode": {
        "kernel": "xla", "query_heads": 4, "kv_heads": 2}}
    assert st["moe_rows"] == {
        "prefill_chunk": {"pairs": 3 * CHUNK, "bound": 3 * CHUNK},
        "decode": {"pairs": 3 * SLOTS, "bound": 3 * SLOTS}}
    assert set(st["moe_tiles"]["decode"].values()) == {"xla"}
    sw = serving.load_stepwise(artifact)
    pool = sw.make_pool()
    assert {k: list(v.shape) for k, v in pool.items()} == {
        k: v["shape"] for k, v in st["specs"].items()}
    # zeroing a slot touches that slot's rings and nothing else
    z = sw.zero_slot({k: jnp.ones_like(v) for k, v in pool.items()}, 1)
    for name in ("cache_window_k", "cache_window_v"):
        assert not np.asarray(z[name][:, 1]).any()
        assert np.asarray(z[name][:, 0]).all()
    assert np.asarray(z["cache_k"]).all() and np.asarray(z["cache_v"]).all()
    with pytest.raises(ValueError, match="scheduler"):
        serving.load_servable(artifact)({"input_ids": np.zeros((1, 4))})


def test_engine_generates_what_the_reference_generates(artifact, f32):
    """Requests of unlike lengths (inside one chunk, over three, on a
    chunk boundary) through chunked prefill and the shared one-token
    step, more requests than slots so that slots and blocks are reused:
    each gives the reference's greedy tokens, as it does alone; the
    engine took the two caches as it takes any state artifact's."""
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
        handles = [eng.submit(p, max_new=k) for p, k in zip(prompts, new)]
        got = [h.result(timeout=300) for h in handles]
        assert got == want
        st = eng.stats()
        assert st["admissions"] == 6 > SLOTS
        assert st["prefill_chunks"] == sum(-(-n // CHUNK) for n in lens)
        assert st["state"]["mixers"][1] == "gqa_window"
        pool = int(np.prod(st["pool_shape"])) * 4
        ring = 3 * SLOTS * 16 * 32 * 4
        assert st["state"]["bytes"] == {
            "cache_k": pool, "cache_v": pool,
            "cache_window_k": ring, "cache_window_v": ring}
        assert st["kv_pool_bytes"] == 2 * pool
        assert st["window_cache_bytes"] == st["state_bytes"] == 2 * ring
        assert st["latent_pool_bytes"] == st["index_pool_bytes"] == 0
        assert st["attn_schedule"]["decode"]["kernel"] == "xla"
        assert st["decode_logits_steps"] == 0
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
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/models/{srv.name}:generate",
            data=json.dumps({"inputs": {"input_ids": [prompt]},
                             "max_new": 6}).encode(),
            headers={"Content-Type": "application/json"})
        body = json.load(urllib.request.urlopen(req, timeout=300))
    finally:
        srv.stop(drain=False)
    assert body["generations"][0] == simulate(params, prompt, 6)


def test_what_the_artifact_refuses_is_said(artifact, f32):
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


def test_spans_say_what_the_two_caches_were_read_for(artifact):
    """``prefill_chunk`` and ``decode_step`` spans of a grouped-query
    state artifact carry ``kv_bytes`` (the contexts' K/V rows of the full
    layers), ``window_bytes`` (ring rows read), ``context_rows``; the
    decode span ``expert_rows`` too."""
    eng = GenerationEngine(serving.load_stepwise(artifact))
    token = 2 * 2 * 32 * 4          # K and V rows of two full layers
    chunk = eng._describe_selection(32 + 1 + np.arange(20), 52)
    assert chunk == {
        "context_rows": 2 * sum(range(33, 53)), "kv_bytes": 52 * token,
        "window_bytes": 20 * 9 * 3 * 2 * 32 * 4}
    feats = {"alive": np.array([1, 0, 1]), "pos": np.array([4, 0, 40]),
             "tok": np.zeros(3, np.int32)}
    step = eng._describe_state_decode(feats)
    assert step["slots"] == 2
    assert step["context_rows"] == 2 * (5 + 41)
    assert step["kv_bytes"] == (5 + 41) * token
    assert step["window_bytes"] == (5 + 9) * 3 * 2 * 32 * 4
    assert "expert_rows" in step and "selected_rows" not in step
    eng.close()

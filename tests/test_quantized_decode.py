"""Quantized decode end-to-end (round 12): int8 weights + int8 paged
KV-cache pool.

- quantize_kv_rows unit properties (error bound, zero rows,
  determinism — the prefix-cache byte-identity foundation),
- paged decode attention over int8 pools: XLA gather path vs a manual
  dequant of the same pools, Pallas scalar-prefetch kernel (interpret
  mode on CPU) vs the gather path, and loud scale/pool validation,
- model level: paged_prefill / decode_step_batched_paged
  quantize-on-write (written bytes exactly quantize_kv_rows of the
  float row; dead-row gating leaves pool AND scale bytes alone),
- export level: quant metadata recording, loud knob validation,
  pool_bytes sizing (int8 holds exactly 2x the bf16 block count at
  equal pool bytes — the capacity acceptance unit test), the quant-off
  bitwise no-op, and validate_quant_meta regressions naming the
  offending export.json field,
- engine + HTTP level: int8 greedy drift vs the full-precision oracle
  within the documented bound, prefix-cache reuse on int8 blocks,
  /stats kv_cache_dtype, and the serving_quant_fallback_total counter
  for pre-quant artifacts.
"""

import dataclasses
import json
import os
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model
from distributed_tensorflow_example_tpu.models.gpt import (GPT, GPTConfig,
                                                           quantize_kv_rows)
from distributed_tensorflow_example_tpu.ops.pallas.decode_attention import (
    paged_decode_attention, paged_tile_friendly)
from distributed_tensorflow_example_tpu.serving import (ServableModel,
                                                        export_generator,
                                                        load_stepwise,
                                                        validate_quant_meta)
from distributed_tensorflow_example_tpu.serving_batch import \
    GenerationEngine
from distributed_tensorflow_example_tpu.serving_cache import BlockPool
from distributed_tensorflow_example_tpu.serving_http import PredictServer

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "experiments"))
from serving_load import INT8_MIN_AGREEMENT, token_agreement  # noqa: E402

PROMPT_LEN = 8
MAX_NEW = 5
SLOTS = 4
BLOCK = 4


# ---------------------------------------------------------------------------
# quantizer unit
# ---------------------------------------------------------------------------

def test_quantize_kv_rows_error_bound_and_zero_rows():
    """|x - q*s| <= s/2 per element (round-to-nearest symmetric int8),
    an all-zero row dequantizes to EXACT zeros (eps floor, no NaN),
    and the bytes are a pure function of the row values — the
    property prefix-cache block sharing rides."""
    rs = np.random.RandomState(0)
    x = rs.randn(3, 7, 4 * 16).astype(np.float32)   # [.., H*D] token rows
    x[1, 2] = 0.0                              # an all-zero row
    q, s = quantize_kv_rows(jnp.asarray(x))
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert q.shape == x.shape and s.shape == (3, 7)
    deq = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    err = np.abs(deq - x)
    assert (err <= np.asarray(s)[..., None] / 2 + 1e-7).all()
    np.testing.assert_array_equal(deq[1, 2], np.zeros(4 * 16))
    # one scale a token row over the same H*D values as the [H, D]
    # plane the 5-D pool quantized: the bytes did not change
    plane = np.abs(x.reshape(3, 7, 4, 16)).max(axis=(-2, -1))
    np.testing.assert_array_equal(
        np.asarray(s), np.maximum(plane, 1e-8) / np.float32(127.0))
    q2, s2 = quantize_kv_rows(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))


# ---------------------------------------------------------------------------
# kernel / op level
# ---------------------------------------------------------------------------

def _quantized_pool(rs, n, bs, h, d):
    kf = rs.randn(n, bs, h * d).astype(np.float32)
    q, s = quantize_kv_rows(jnp.asarray(kf))
    return np.asarray(q), np.asarray(s)


def test_int8_paged_xla_matches_manual_dequant():
    """The XLA gather path's fused dequant == dequantizing the pools
    up front and running the float gather path, bit for bit."""
    rs = np.random.RandomState(1)
    b, h, d, bs, nb = 3, 4, 32, 4, 3
    n = 1 + b * nb
    kq, ks = _quantized_pool(rs, n, bs, h, d)
    vq, vs = _quantized_pool(rs, n, bs, h, d)
    q = rs.randn(b, h, d).astype(np.float32)
    bt = rs.permutation(np.arange(1, n))[:b * nb].reshape(b, nb)
    bt = bt.astype(np.int32)
    pos = np.array([2, 7, 11], np.int32)
    pad = np.array([0, 1, 0], np.int32)
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
        block_tables=bt, pos=pos, pad=pad, k_scale=jnp.asarray(ks),
        v_scale=jnp.asarray(vs), impl="xla")
    kf = (kq.astype(np.float32) * ks[..., None]).astype(np.float32)
    vf = (vq.astype(np.float32) * vs[..., None]).astype(np.float32)
    want = paged_decode_attention(jnp.asarray(q), jnp.asarray(kf),
                                  jnp.asarray(vf), block_tables=bt,
                                  pos=pos, pad=pad, impl="xla")
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_int8_paged_kernel_matches_gather_reference():
    """The scalar-prefetch kernel's ALGEBRAIC dequant (scales folded
    into score columns / probabilities) vs the gather path, interpret
    mode on CPU — tier-1 covers both int8 impls (CI satellite)."""
    rs = np.random.RandomState(2)
    b, h, d, bs, nb = 2, 2, 64, 128, 3
    assert paged_tile_friendly(bs, h, d)
    n = 1 + b * nb
    kq, ks = _quantized_pool(rs, n, bs, h, d)
    vq, vs = _quantized_pool(rs, n, bs, h, d)
    q = rs.randn(b, h, d).astype(np.float32)
    bt = np.arange(1, 1 + b * nb, dtype=np.int32).reshape(b, nb)
    bt[0, 2] = 0                    # beyond pos: never read
    pos = np.array([130, 380], np.int32)
    pad = np.array([3, 0], np.int32)
    kw = dict(block_tables=bt, pos=pos, pad=pad,
              k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    want = paged_decode_attention(jnp.asarray(q), jnp.asarray(kq),
                                  jnp.asarray(vq), impl="xla", **kw)
    got = paged_decode_attention(jnp.asarray(q), jnp.asarray(kq),
                                 jnp.asarray(vq), impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("qdtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["mixed", "pos0", "pos127", "pos128",
                                  "poslast", "pad", "nulltable", "flat"])
@pytest.mark.parametrize("heads,head_dim", [(12, 64), (4, 128)])
def test_int8_paged_kernel_cases(heads, head_dim, case, qdtype):
    """int8 pools with their scale rows, kernel against gather: every
    head of a block at once at 12 x 64 and 4 x 128, pos on the block
    edges, a pad window, rows that are not alive, and layer 1 of a flat
    [L*N, Bs, H*D] view; under a float32 query and a bfloat16 one (the
    int8 values are exact in either: same scores)."""
    rs = np.random.RandomState(heads + len(case))
    h, d, b, bs, nb = heads, head_dim, 3, 128, 3
    n = 1 + b * nb
    layers = 2 if case == "flat" else 1
    kq, ks = _quantized_pool(rs, layers * n, bs, h, d)
    vq, vs = _quantized_pool(rs, layers * n, bs, h, d)
    q = jnp.asarray(rs.randn(b, h, d), qdtype)
    bt = rs.permutation(np.arange(1, n)).astype(np.int32).reshape(b, nb)
    pos = np.array([130, nb * bs - 1, 5], np.int32)
    pad = np.zeros(b, np.int32)
    if case.startswith("pos"):
        pos[:] = {"pos0": 0, "pos127": 127, "pos128": 128,
                  "poslast": nb * bs - 1}[case]
    elif case == "pad":
        pad[:] = [3, 140, 5]
    elif case == "nulltable":
        bt[0] = bt[2] = 0
        pos[[0, 2]] = 0
    elif case == "flat":
        bt = bt + n
    kw = dict(block_tables=bt, pos=pos, pad=pad,
              k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    want = paged_decode_attention(q, jnp.asarray(kq), jnp.asarray(vq),
                                  impl="xla", **kw)
    got = paged_decode_attention(q, jnp.asarray(kq), jnp.asarray(vq),
                                 impl="pallas", **kw)
    assert got.dtype == q.dtype
    # (the gather rounds each dequantized row to the query's dtype, the
    # kernel keeps the scales in float32: bfloat16 agrees to its rounding)
    tol = 2e-5 if qdtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(want, np.float32),
                               np.asarray(got, np.float32),
                               rtol=tol, atol=tol)


def test_int8_paged_scale_validation():
    """Scales and int8 pools travel together — one without the other
    (or mis-shaped) is a loud error, never a silent garbage read."""
    rs = np.random.RandomState(3)
    b, h, d, bs, nb = 1, 2, 32, 4, 2
    n = 1 + b * nb
    kq, ks = _quantized_pool(rs, n, bs, h, d)
    q = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
    bt = np.arange(1, 1 + nb, dtype=np.int32).reshape(b, nb)
    pos = np.zeros(b, np.int32)
    pad = np.zeros(b, np.int32)
    kw = dict(block_tables=bt, pos=pos, pad=pad)
    kqj, ksj = jnp.asarray(kq), jnp.asarray(ks)
    with pytest.raises(ValueError, match="together"):
        paged_decode_attention(q, kqj, kqj, k_scale=ksj, **kw)
    with pytest.raises(ValueError, match="k_scale/v_scale"):
        paged_decode_attention(q, kqj, kqj, **kw)
    with pytest.raises(ValueError, match="int8 pools"):
        kf = jnp.asarray(kq.astype(np.float32))
        paged_decode_attention(q, kf, kf, k_scale=ksj, v_scale=ksj,
                               **kw)
    with pytest.raises(ValueError, match="scale shape"):
        paged_decode_attention(q, kqj, kqj, k_scale=ksj[:, :2],
                               v_scale=ksj, **kw)


# ---------------------------------------------------------------------------
# model level: quantize-on-write
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_layer_model():
    """layers=1 makes the written K/V rows independent of the cache
    path (qkv is computed BEFORE attention), so quantize-on-write can
    be asserted byte-exact against quantize_kv_rows of the float
    path's own written row."""
    m = GPT(dataclasses.replace(GPTConfig.tiny(), layers=1))
    out = m.init(jax.random.key(0))
    params = out[0] if isinstance(out, tuple) else out
    return m, params


def test_paged_prefill_int8_writes_quantized_blocks(one_layer_model):
    """int8 paged_prefill == float paged_prefill + quantize_kv_rows of
    every written token row, byte for byte — and the logits (computed
    before any cache read) are identical."""
    m, params = one_layer_model
    c = m.cfg
    l, h, d = c.layers, c.heads, m.head_dim
    rs = np.random.RandomState(4)
    p = 6
    ids = np.zeros((1, PROMPT_LEN), np.int32)
    mask = np.zeros((1, PROMPT_LEN), np.int32)
    ids[0, :p] = rs.randint(0, c.vocab_size, (p,))
    mask[0, :p] = 1
    tr = np.array([2, 4], np.int32)
    zf = jnp.zeros((l, 6, BLOCK, h * d), jnp.float32)
    zq = jnp.zeros((l, 6, BLOCK, h * d), jnp.int8)
    zs = jnp.zeros((l, 6, BLOCK), jnp.float32)
    lg_f, kf, vf = m.paged_prefill(params, jnp.asarray(ids),
                                   jnp.asarray(mask), zf, zf,
                                   jnp.asarray(tr))
    lg_q, kq, vq, ksc, vsc = m.paged_prefill(
        params, jnp.asarray(ids), jnp.asarray(mask), zq, zq,
        jnp.asarray(tr), k_scale=zs, v_scale=zs)
    np.testing.assert_array_equal(np.asarray(lg_f), np.asarray(lg_q))
    for fp, qp, sp in ((kf, kq, ksc), (vf, vq, vsc)):
        wq, ws = quantize_kv_rows(np.asarray(fp)[:, tr])
        np.testing.assert_array_equal(np.asarray(qp)[:, tr],
                                      np.asarray(wq))
        np.testing.assert_array_equal(np.asarray(sp)[:, tr],
                                      np.asarray(ws))
    # determinism: a second prefill of the same tokens produces the
    # same bytes — what lets the prefix cache share int8 blocks
    _, kq2, _, ksc2, _ = m.paged_prefill(
        params, jnp.asarray(ids), jnp.asarray(mask), zq, zq,
        jnp.asarray(tr), k_scale=zs, v_scale=zs)
    np.testing.assert_array_equal(np.asarray(kq), np.asarray(kq2))
    np.testing.assert_array_equal(np.asarray(ksc), np.asarray(ksc2))


def test_paged_decode_step_int8_write_and_dead_row_gating(
        one_layer_model):
    """The int8 decode step quantizes its new row on write (bytes ==
    quantize_kv_rows of the float path's written row) and a dead row
    leaves pool AND scale bytes untouched."""
    m, params = one_layer_model
    c = m.cfg
    l, h, d = c.layers, c.heads, m.head_dim
    rs = np.random.RandomState(5)
    b, bs, nb = 2, 4, 2
    n = 1 + b * nb
    stacked = m.stack_decode_params(params)
    bt = (1 + np.arange(b * nb).reshape(b, nb)).astype(np.int32)
    # seed the pools with an already-quantized history
    hist = rs.randn(l, n, bs, h * d).astype(np.float32)
    q, s = quantize_kv_rows(jnp.asarray(hist))
    pools_f = {"k": jnp.asarray(np.asarray(q, np.float32)
                                * np.asarray(s)[..., None]),
               "v": jnp.asarray(np.asarray(q, np.float32)
                                * np.asarray(s)[..., None])}
    pools_q = {"k": q, "v": q, "k_scale": s, "v_scale": s}
    tok = jnp.asarray(rs.randint(0, c.vocab_size, (b,)), jnp.int32)
    pos = jnp.asarray([2, 5], jnp.int32)
    pad = jnp.zeros((b,), jnp.int32)
    alive = jnp.asarray([1, 0], jnp.int32)     # row 1 is DEAD
    _, new_f = m.decode_step_batched(
        params, stacked,
        {x: jnp.asarray(np.asarray(pools_f[x])[:, bt].reshape(
            l, b, nb * bs, h, d)) for x in ("k", "v")},
        tok, pos, pad, alive, decode_attention="xla")
    lg_q, new_q = m.decode_step_batched_paged(
        params, stacked, pools_q, bt, tok, pos, pad, alive,
        decode_attention="xla")
    assert lg_q.shape == (b, c.vocab_size)
    # live row 0: written bytes == quantize of the float path's row
    pb, off = bt[0, int(pos[0]) // bs], int(pos[0]) % bs
    for x, sx in (("k", "k_scale"), ("v", "v_scale")):
        row_f = np.asarray(new_f[x])[:, 0, int(pos[0])]     # [L, H, D]
        wq, ws = quantize_kv_rows(jnp.asarray(row_f.reshape(l, h * d)))
        np.testing.assert_array_equal(
            np.asarray(new_q[x])[:, pb, off], np.asarray(wq))
        np.testing.assert_array_equal(
            np.asarray(new_q[sx])[:, pb, off], np.asarray(ws))
    # dead row 1: every one of its table's blocks byte-identical
    for x in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(new_q[x])[:, bt[1]],
            np.asarray(pools_q[x])[:, bt[1]])


def _step_on_5d_pools(m, params, stacked, pools, bt, tok, pos, pad, alive):
    """The paged decode step as it stood over ``[L, N, Bs, H, D]`` pools
    (before PR 31): the pools scanned as ``xs`` beside the stacked
    weights and stacked back as ``ys``, the new [H, D] plane written
    into the layer's slice (int8: one scale over the plane), the slot's
    run gathered 5-D, dequantized and attended by the slab reference.
    The oracle the flat, carried step must match to the byte."""
    from jax import lax

    from distributed_tensorflow_example_tpu.ops import nn
    from distributed_tensorflow_example_tpu.ops.pallas.decode_attention \
        import xla_decode_attention
    c = m.cfg
    b, nb = bt.shape
    bs = pools["k"].shape[2]
    names = sorted(pools)
    live = jnp.asarray(alive) != 0
    h, _ = m._embed(params, tok[:, None], (pos - pad)[:, None], rng=None,
                    train=False)
    pbid, off = bt[jnp.arange(b), pos // bs], pos % bs

    def body(h, xs):
        lp, cache = xs[0], dict(zip(names, xs[1:]))
        qkv = nn.dense(m._dequant(lp["qkv"]), nn.layernorm(lp["ln1"], h),
                       dtype=m.dtype)
        q, k, v = [x.reshape(b, c.heads, m.head_dim)
                   for x in jnp.split(qkv, 3, axis=-1)]
        ctx_kv = []
        for name, fresh in (("k", k), ("v", v)):
            pool, spool = cache[name], cache.get(name + "_scale")
            if spool is not None:
                xf = fresh.astype(jnp.float32)
                sc = jnp.maximum(jnp.max(jnp.abs(xf), axis=(-2, -1)),
                                 1e-8) / 127.0
                fresh = jnp.round(xf / sc[:, None, None]).astype(jnp.int8)
                spool = cache[name + "_scale"] = spool.at[pbid, off].set(
                    jnp.where(live, sc, spool[pbid, off]))
            pool = cache[name] = pool.at[pbid, off].set(jnp.where(
                live[:, None, None], fresh.astype(pool.dtype),
                pool[pbid, off]))
            g = pool[bt]                            # [B, NB, Bs, H, D]
            if spool is not None:
                g = (g.astype(jnp.float32)
                     * spool[bt][..., None, None]).astype(q.dtype)
            ctx_kv.append(g.reshape(b, nb * bs, c.heads, m.head_dim))
        ctx = xla_decode_attention(q, *ctx_kv, pos=pos, pad=pad)
        a = nn.dense(m._dequant(lp["o"]), ctx.reshape(b, c.hidden),
                     dtype=m.dtype)
        h = h + a.astype(h.dtype)
        f = nn.dense(m._dequant(lp["ffn_in"]), nn.layernorm(lp["ln2"], h),
                     dtype=m.dtype)
        f = jax.nn.gelu(f.astype(jnp.float32)).astype(m.dtype)
        h = h + nn.dense(m._dequant(lp["ffn_out"]), f,
                         dtype=m.dtype).astype(h.dtype)
        return h, tuple(cache[name] for name in names)

    h, ys = lax.scan(body, h[:, 0],
                     (stacked, *(pools[name] for name in names)))
    h = nn.layernorm(params["ln_f"], h)
    return m.lm_logits(params, h[:, None])[:, 0], dict(zip(names, ys))


@pytest.mark.parametrize("kv", ["float32", "int8"])
@pytest.mark.parametrize("weights", [None, "int8"])
def test_flat_carried_step_matches_the_5d_oracle(kv, weights):
    """Same mathematics, same bytes: ``decode_step_batched_paged`` over
    ``[L, N, Bs, H*D]`` pools carried through the layer scan against the
    old arithmetic over ``[L, N, Bs, H, D]`` pools, two steps in a row:
    identical logits and identical pools, float and int8, a live row, a
    live row at a block's last offset, and a dead row whose table names
    the null block (which keeps every byte it held)."""
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    params = m.init(jax.random.key(3))
    c = m.cfg
    l, hh, d = c.layers, c.heads, m.head_dim
    rs = np.random.RandomState(6)
    b, bs, nb = 3, 4, 3
    n = 2 + b * nb
    stacked = m.stack_decode_params(params, weight_quant=weights)
    bt = (1 + np.arange(b * nb).reshape(b, nb)).astype(np.int32)
    bt[2] = 0                               # the dead row: null block
    flat = {x: rs.randn(l, n, bs, hh * d).astype(np.float32)
            for x in ("k", "v")}
    if kv == "int8":
        for x in ("k", "v"):
            flat[x], flat[x + "_scale"] = quantize_kv_rows(
                jnp.asarray(flat[x]))
    flat = {x: jnp.asarray(v) for x, v in flat.items()}
    five = {x: v.reshape(l, n, bs, hh, d) if v.ndim == 4 else v
            for x, v in flat.items()}
    before = {x: np.asarray(v) for x, v in flat.items()}
    pad = jnp.asarray([0, 1, 0], jnp.int32)
    alive = jnp.asarray([1, 1, 0], jnp.int32)
    for step in range(2):
        tok = jnp.asarray(rs.randint(0, c.vocab_size, (b,)), jnp.int32)
        pos = jnp.asarray([5 + step, 2 + step, 9], jnp.int32)
        want_lg, five = _step_on_5d_pools(m, params, stacked, five,
                                          jnp.asarray(bt), tok, pos, pad,
                                          alive)
        got_lg, flat = m.decode_step_batched_paged(
            params, stacked, flat, bt, tok, pos, pad, alive,
            decode_attention="xla")
        np.testing.assert_array_equal(np.asarray(want_lg),
                                      np.asarray(got_lg))
        assert set(flat) == set(five)
        for x in flat:
            np.testing.assert_array_equal(
                np.asarray(five[x]).reshape(flat[x].shape),
                np.asarray(flat[x]), err_msg=f"{x} after step {step}")
    for x, was in before.items():
        now = np.asarray(flat[x])
        assert (now[:, bt[0, 1], 1] != was[:, bt[0, 1], 1]).any()  # pos 5
        np.testing.assert_array_equal(now[:, 0], was[:, 0])   # null block


# ---------------------------------------------------------------------------
# export level
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    out = m.init(jax.random.key(0))
    params = out[0] if isinstance(out, tuple) else out
    return m, params


def _export(m, params, d, **kw):
    kw.setdefault("prompt_len", PROMPT_LEN)
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("batch_size", 1)
    kw.setdefault("platforms", ("cpu",))
    return export_generator(m, params, d, **kw)


def test_export_quant_knob_validation(tiny_model, tmp_path):
    m, params = tiny_model
    d = str(tmp_path / "x")
    with pytest.raises(ValueError, match="paged=True"):
        _export(m, params, d, ragged=True, stepwise=True,
                kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="requires paged=True"):
        _export(m, params, d, ragged=True, stepwise=True,
                pool_bytes=1 << 20)
    with pytest.raises(ValueError, match="not both"):
        _export(m, params, d, ragged=True, stepwise=True, paged=True,
                block_size=BLOCK, num_blocks=48, pool_bytes=1 << 20)
    with pytest.raises(ValueError, match="weight_quant"):
        _export(m, params, d, weight_quant="int4")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _export(m, params, d, ragged=True, stepwise=True, paged=True,
                kv_cache_dtype="fp8")


@pytest.fixture(scope="module")
def int8_dir(tmp_path_factory, tiny_model):
    """One int8 paged export (int8 weights + int8 KV pool) shared by
    the metadata/engine/HTTP tests."""
    d = str(tmp_path_factory.mktemp("int8"))
    m, params = tiny_model
    _export(m, params, d, ragged=True, stepwise=True, slots=SLOTS,
            paged=True, block_size=BLOCK, num_blocks=48,
            weight_quant="int8", kv_cache_dtype="int8")
    return d


def test_int8_export_metadata_and_pool(int8_dir):
    with open(os.path.join(int8_dir, "export.json")) as f:
        meta = json.load(f)
    assert meta["quant_schema"] == 1
    assert meta["weight_quant"] == "int8"
    sm = meta["stepwise"]
    assert sm["kv_cache_dtype"] == "int8"
    assert sm["cache_dtype"] == "int8"
    l_, n, bs = sm["pool_shape"][0], sm["pool_shape"][1], \
        sm["pool_shape"][2]
    assert sm["kv_scale_shape"] == [l_, n, bs]
    assert sm["kv_scale_dtype"] == "float32"
    assert sm["block_bytes"] > 0
    sw = load_stepwise(int8_dir)
    assert sw.kv_cache_dtype == "int8"
    pool = sw.make_pool()
    assert set(pool) == {"cache_k", "cache_v", "cache_k_scale",
                         "cache_v_scale"}
    assert pool["cache_k"].dtype == jnp.int8
    assert pool["cache_k_scale"].dtype == jnp.float32
    assert pool["cache_k_scale"].shape == (l_, n, bs)


def test_equal_pool_bytes_int8_doubles_blocks(tiny_model, tmp_path):
    """THE capacity acceptance unit test: at the same pool_bytes
    budget, the int8 export holds exactly 2x the bf16 usable block
    count (itemsize 2 -> 1), and BlockPool.from_bytes mirrors the
    sizing rule."""
    m, params = tiny_model
    budget = 1 << 20
    counts = {}
    for dtype in ("bf16", "int8"):
        d = str(tmp_path / dtype)
        _export(m, params, d, ragged=True, stepwise=True, slots=SLOTS,
                paged=True, block_size=BLOCK, pool_bytes=budget,
                kv_cache_dtype=dtype)
        sm = load_stepwise(d).step_meta
        counts[dtype] = int(sm["num_blocks"]) - 1       # minus null
    assert counts["int8"] == 2 * counts["bf16"]
    assert counts["int8"] >= 2                          # non-trivial
    bp = BlockPool.from_bytes(budget, 1024)
    assert bp.usable == budget // 1024
    with pytest.raises(ValueError, match="block_bytes"):
        BlockPool.from_bytes(budget, 0)


def test_block_pool_tracks_peak_in_use():
    bp = BlockPool(6)
    run = bp.alloc(3)
    assert bp.in_use == 3 and bp.peak_in_use == 3
    bp.release(run)
    assert bp.in_use == 0 and bp.peak_in_use == 3       # high-water
    bp.alloc(2)
    assert bp.peak_in_use == 3
    bp.alloc(2)
    assert bp.peak_in_use == 4


def test_quant_off_is_bitwise_noop(tiny_model, tmp_path):
    """weight_quant='off' + kv_cache_dtype='auto' normalize to the
    EXACT default export: same greedy bytes from the monolithic
    artifact, same pool dtype/bytes from the stepwise pair."""
    m, params = tiny_model
    rs = np.random.RandomState(6)
    ids = rs.randint(0, 1000, (1, PROMPT_LEN), dtype=np.int32)
    mask = np.ones_like(ids)
    outs, metas = [], []
    for name, kw in (("default", {}),
                     ("off", {"weight_quant": "off",
                              "kv_cache_dtype": "auto"})):
        d = str(tmp_path / name)
        _export(m, params, d, ragged=True, stepwise=True, slots=2,
                paged=True, block_size=BLOCK, num_blocks=24, **kw)
        sv = ServableModel(d)
        outs.append(np.asarray(sv({"input_ids": ids,
                                   "prompt_mask": mask})))
        metas.append(sv.meta)
    np.testing.assert_array_equal(outs[0], outs[1])
    for m0 in metas:
        assert m0["weight_quant"] is None
        assert m0["stepwise"]["kv_cache_dtype"] == \
            m0["stepwise"]["cache_dtype"]
        assert "kv_scale_shape" not in m0["stepwise"]
    assert metas[0]["stepwise"]["pool_shape"] == \
        metas[1]["stepwise"]["pool_shape"]
    sw = load_stepwise(str(tmp_path / "off"))
    assert set(sw.make_pool()) == {"cache_k", "cache_v"}


# ---------------------------------------------------------------------------
# metadata hardening
# ---------------------------------------------------------------------------

def _int8_meta():
    return {
        "quant_schema": 1, "weight_quant": "int8",
        "stepwise": {"paged": True, "kv_cache_dtype": "int8",
                     "cache_dtype": "int8",
                     "pool_shape": [2, 9, 4, 4 * 32],
                     "kv_scale_shape": [2, 9, 4],
                     "kv_scale_dtype": "float32"}}


def test_validate_quant_meta_regressions():
    validate_quant_meta(_int8_meta())                   # the good case
    validate_quant_meta({})                             # pre-quant: ok
    m = _int8_meta()
    m["quant_schema"] = 99
    with pytest.raises(ValueError, match="quant_schema"):
        validate_quant_meta(m)
    m = _int8_meta()
    m["weight_quant"] = "int4"
    with pytest.raises(ValueError, match="weight_quant"):
        validate_quant_meta(m)
    m = _int8_meta()
    m["stepwise"]["paged"] = False
    with pytest.raises(ValueError, match="paged"):
        validate_quant_meta(m)
    m = _int8_meta()
    m["stepwise"]["kv_scale_shape"] = [2, 9, 8]
    with pytest.raises(ValueError, match="kv_scale_shape"):
        validate_quant_meta(m)
    m = _int8_meta()
    m["stepwise"]["kv_scale_dtype"] = "notadtype"
    with pytest.raises(ValueError, match="kv_scale_dtype"):
        validate_quant_meta(m)
    m = _int8_meta()
    m["stepwise"]["kv_cache_dtype"] = "alsonotadtype"
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        validate_quant_meta(m)


def test_loader_rejects_corrupt_quant_meta(int8_dir, tmp_path):
    """The loaders validate at LOAD time and the error names the
    artifact field — no shape error deep in the scan."""
    import shutil
    d = str(tmp_path / "corrupt")
    shutil.copytree(int8_dir, d)
    p = os.path.join(d, "export.json")
    with open(p) as f:
        meta = json.load(f)
    meta["stepwise"]["kv_scale_shape"] = [1, 2, 3]
    with open(p, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="kv_scale_shape"):
        load_stepwise(d)
    with pytest.raises(ValueError, match="kv_scale_shape"):
        ServableModel(d)
    meta["quant_schema"] = 99
    with open(p, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="quant_schema"):
        load_stepwise(d)


# ---------------------------------------------------------------------------
# engine + HTTP level
# ---------------------------------------------------------------------------

def _oracle(m, params, prompt, max_new=MAX_NEW):
    ids = np.zeros((1, PROMPT_LEN), np.int32)
    mask = np.zeros((1, PROMPT_LEN), np.int32)
    ids[0, :prompt.size] = prompt
    mask[0, :prompt.size] = 1
    return np.asarray(m.generate(params, jnp.asarray(ids), max_new,
                                 prompt_mask=jnp.asarray(mask)))[0].tolist()


def _prompts(n, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 1000, (int(rs.randint(1, PROMPT_LEN + 1)),)
                       ).astype(np.int32) for _ in range(n)]


def test_engine_int8_drift_within_bound_and_stats(int8_dir, tiny_model):
    """Engine-level drift gate: int8 greedy token streams agree with
    the full-precision oracle at >= the documented bound, and /stats
    reports the quantized pool's dtype + residency peak."""
    m, params = tiny_model
    prompts = _prompts(SLOTS * 2, seed=20)
    eng = GenerationEngine(load_stepwise(int8_dir))
    assert eng.kv_cache_dtype == "int8"
    futs = [eng.submit(p) for p in prompts]
    eng.start()
    try:
        got = [f.result(timeout=120) for f in futs]
    finally:
        eng.close()
    want = [_oracle(m, params, p) for p in prompts]
    agreement = token_agreement([got], [want])
    assert agreement >= INT8_MIN_AGREEMENT, (
        f"int8 drift gate: agreement {agreement} < "
        f"{INT8_MIN_AGREEMENT}")
    s = eng.stats()
    assert s["kv_cache_dtype"] == "int8"
    assert s["bytes_resident_peak"] > 0


def test_engine_int8_prefix_reuse_stays_deterministic(int8_dir):
    """Quantize-on-write commutes with the prefix cache: an identical
    repeat exact-hits (ZERO new prefills) and replays the SAME tokens
    — shared int8 blocks mount byte-identically."""
    prompts = _prompts(3, seed=21)
    eng = GenerationEngine(load_stepwise(int8_dir))
    futs = [eng.submit(p) for p in prompts]
    eng.start()
    try:
        first = [f.result(timeout=120) for f in futs]
        pre = eng.prefills
        second = [eng.submit(p).result(timeout=120) for p in prompts]
    finally:
        eng.close()
    assert eng.prefills == pre, "repeat prompts must not prefill"
    assert first == second
    assert eng.stats()["prefix_cache_hits"] >= len(prompts)


def test_http_int8_generate_stats_and_metrics(int8_dir, tiny_model):
    """HTTP-level drift gate + observability: :generate over the int8
    artifact tracks the oracle within the bound, /stats carries
    kv_cache_dtype, and /metrics exposes the quant counters."""
    m, params = tiny_model
    prompts = _prompts(4, seed=22)
    with PredictServer(int8_dir) as srv:
        assert srv.scheduler == "on"
        got = []
        for p in prompts:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/models/{srv.name}"
                ":generate",
                data=json.dumps(
                    {"inputs": {"input_ids": [p.tolist()]}}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as r:
                got.append(json.loads(r.read())["generations"][0])
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/stats") as r:
            stats = json.loads(r.read())["generate"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics") as r:
            prom = r.read().decode()
    want = [_oracle(m, params, p) for p in prompts]
    agreement = token_agreement([got], [want])
    assert agreement >= INT8_MIN_AGREEMENT
    assert stats["kv_cache_dtype"] == "int8"
    assert "serving_quant_fallback_total 0" in prom
    assert "serving_kv_cache_bytes_per_token" in prom


def test_int8_bytes_per_token_below_bf16(tiny_model, tmp_path):
    """The residency observable: one cached token costs fewer bytes
    under int8 (payload halves vs bf16; the f32 scale rows cost
    2*L*4 of it back)."""
    m, params = tiny_model
    vals = {}
    for dtype in ("bf16", "int8"):
        d = str(tmp_path / dtype)
        _export(m, params, d, ragged=True, stepwise=True, slots=2,
                paged=True, block_size=BLOCK, num_blocks=24,
                kv_cache_dtype=dtype)
        eng = GenerationEngine(load_stepwise(d))
        vals[dtype] = eng.registry.snapshot()[
            "serving_kv_cache_bytes_per_token"]["value"]
        eng.close()
    assert vals["int8"] < vals["bf16"]


def test_quant_fallback_counter_on_prequant_artifact(tiny_model,
                                                     tmp_path):
    """An artifact exported before the quant schema (no quant_schema
    key) still serves, but serving_quant_fallback_total counts it —
    the operator-visible signal that no quantized path is active."""
    m, params = tiny_model
    d = str(tmp_path / "prequant")
    _export(m, params, d)
    p = os.path.join(d, "export.json")
    with open(p) as f:
        meta = json.load(f)
    del meta["quant_schema"]
    del meta["weight_quant"]
    with open(p, "w") as f:
        json.dump(meta, f)
    with PredictServer(d) as srv:
        snap = srv.registry.snapshot()
        assert snap["serving_quant_fallback_total"]["value"] == 1
    # a modern (schema-carrying) artifact does NOT count
    d2 = str(tmp_path / "modern")
    _export(m, params, d2)
    with PredictServer(d2) as srv:
        assert srv.registry.snapshot()[
            "serving_quant_fallback_total"]["value"] == 0


# ---------------------------------------------------------------------------
# CLI plumbing
# ---------------------------------------------------------------------------

def test_cli_gen_weight_quant_guarded_without_export():
    from distributed_tensorflow_example_tpu.cli.train import main
    with pytest.raises(SystemExit, match="gen_weight_quant"):
        main(["--model", "gpt_tiny", "--train_steps", "1",
              "--batch_size", "8", "--gen_weight_quant", "int8"])


def test_cli_gen_weight_quant_reaches_artifact(tmp_path):
    """--gen_weight_quant int8 lands in the exported artifact's quant
    metadata (the config→CLI plumbing, end to end)."""
    from distributed_tensorflow_example_tpu.cli.train import main
    d = str(tmp_path / "gen")
    rc = main(["--model", "gpt_tiny", "--train_steps", "2",
               "--batch_size", "8", "--export_generator", d,
               "--gen_prompt_len", "8", "--gen_max_new", "4",
               "--gen_weight_quant", "int8"])
    assert rc == 0
    with open(os.path.join(d, "export.json")) as f:
        meta = json.load(f)
    assert meta["weight_quant"] == "int8"
    assert meta["quant_schema"] == 1

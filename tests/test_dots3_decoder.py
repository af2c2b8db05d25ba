"""A decoder that SELECTS the rows it attends to (``models/decoder.py``,
``layer_types``: dots3-note-prev's layers at test widths) against its
plain reference (``benchmark/reference/dots3-note-prev.py``), seeded
random weights: the indexer's scores and the selection without a sort,
selected attention in its dense and gathered forms, the window layers'
ring, chunked prefill and one-token decode through the three caches
(prompts that cross a chunk of 32, a block of 16, the window of 9 and the
top-16), slot reuse, the artifact, the engine, and the other served
models' programs."""

import hashlib
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
from distributed_tensorflow_example_tpu.ops import dsa, mla  # noqa: E402
from distributed_tensorflow_example_tpu.serving_batch import (  # noqa: E402
    GenerationEngine)

ref = load_module(os.path.join(ROOT, "benchmark", "reference",
                               "dots3-note-prev.py"))
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                     "dots3-note-prev.json")))
CFG = CONFIG["rehearsal"]["sizes"]
SLOTS, BS, CHUNK, PROMPT, NEW = 3, 16, 32, 96, 24
NB = 8                                      # blocks a slot


def build(dtype: str, seed: int = 7):
    model = get_model("dots3_note_tiny", TrainConfig(
        model="dots3_note_tiny", dtype=dtype, param_dtype=dtype))
    for k, v in CONFIG["rehearsal"]["model_cfg"].items():
        setattr(model.cfg, k, v)
    params = weights_by_range.make_params(ref.param_spec(CFG), seed, dtype)
    return model, params


@pytest.fixture(scope="module")
def f32():
    return build("float32")


# ---- (a) the description ------------------------------------------------

def test_registry_builds_the_layers_from_a_description():
    big = get_model("dots3_note", TrainConfig(model="dots3_note",
                                              num_layers=5))
    c = big.cfg
    assert list(c.layer_types) == CONFIG["layer_types"]
    assert [c.mixer(i) for i in range(5)] == [
        "mla_sparse", "mla_sparse", "mla_window", "mla_window", "mla_window"]
    full, win = c.geometry("mla_sparse"), c.geometry("mla_window")
    assert (full.heads, full.q_rank, full.rank, full.nope, full.pe, full.v,
            full.row) == (128, 1024, 512, 128, 64, 128, 640)
    assert (win.heads, win.q_rank, win.rank, win.nope, win.pe, win.v,
            win.row) == (64, 1024, 1024, 192, 64, 128, 1152)
    assert (full.theta, win.theta) == (8e7, 5e4)
    assert full.scale == 192 ** -0.5 and win.scale == 256 ** -0.5
    assert (c.window, c.ring, c.index_topk) == (513, 528, 2048)
    for key, attr in (("hidden_size", "hidden"), ("index_n_heads",
                      "index_heads"), ("index_head_dim", "index_head_dim"),
                      ("moe_intermediate_size", "expert_width"),
                      ("intermediate_size", "dense_width"),
                      ("num_experts_per_tok", "experts_per_token")):
        assert getattr(c, attr) == CONFIG[key], key
    assert c.experts == CONFIG["published"]["n_routed_experts"]
    specs = big.state_specs(slots=24, num_blocks=6145, block_size=128)
    assert {k: (v["shape"], v["per"]) for k, v in specs.items()} == {
        "cache_latent": ([2, 6145, 128, 640], "block"),
        "cache_index": ([2, 6145, 128, 128], "block"),
        "cache_window": ([3, 24, 528, 1152], "slot")}
    # the weights of the cell's share, counted from the spec: 8.2 GB
    n = sum(int(np.prod(shape)) for shape, _ in ref.param_spec(
        CONFIG).values())
    assert abs(2 * n / 1e9 - 8.17) < 0.05
    with pytest.raises(ValueError, match="layer_types"):
        from distributed_tensorflow_example_tpu.models.decoder import (
            BlockDecoder, DecoderBlockConfig)
        bad = DecoderBlockConfig.dots3_note_tiny()
        bad.block_length = 4
        BlockDecoder(bad)


# ---- (b) the indexer and the selection ----------------------------------

def _plain_scores(q, w, keys, qpos):
    s = jnp.einsum("qjd,kd->qjk", q, keys, precision="highest")
    s = jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)
    return jnp.where(jnp.arange(keys.shape[0])[None] <= qpos[:, None], s,
                     -jnp.inf)


def _index_case(seed=0, t=64, j=3, d=16, nb=6, bs=16):
    rs = np.random.RandomState(seed)
    keys = jnp.asarray(rs.randn(t, d), jnp.float32)
    q = jnp.asarray(rs.randn(t, j, d), jnp.float32)
    w = jnp.asarray(rs.randn(t, j), jnp.float32)
    table = np.asarray(rs.permutation(np.arange(1, 1 + nb)), np.int32)
    pool = jnp.asarray(rs.randn(1 + nb, bs, d), jnp.float32) * 50.0
    pool = pool.at[table[:t // bs]].set(keys.reshape(t // bs, bs, d))
    return keys, q, w, table, pool


def test_chunk_and_step_scores_are_the_plain_sum_over_heads():
    """A chunk of 32 rows at start 32 and one row a slot, through the
    block table, against the einsum over every key; keys past the row are
    -inf, and the pool's other blocks (garbage) are never read."""
    keys, q, w, table, pool = _index_case()
    want = _plain_scores(q, w, keys, jnp.arange(64))
    got = dsa.chunk_scores(q[32:], w[32:], pool, table, 32, width=96)
    np.testing.assert_allclose(got[:, :64], want[32:], rtol=1e-5, atol=1e-5)
    assert np.isneginf(np.asarray(got[:, 64:])).all()
    tables = np.stack([table, table])
    pos = np.array([63, 40], np.int32)
    step = dsa.step_scores(q[pos], w[pos], pool, tables, jnp.asarray(pos))
    np.testing.assert_allclose(step[:, :64], want[pos], rtol=1e-5,
                               atol=1e-5)
    assert np.isneginf(np.asarray(step[1, 41:])).all()


@pytest.mark.parametrize("case", ["random", "ties", "short", "zeros"])
def test_top_k_mask_is_top_k_without_a_sort(case):
    """The bisection's mask is ``lax.top_k``'s set: on random scores, on
    scores with many equal values at the k-th (the lower positions win),
    on rows with fewer candidates than k, and on signed zeros."""
    rs = np.random.RandomState(3)
    r, t, k = 12, 200, 16
    x = rs.randn(r, t).astype(np.float32)
    if case == "ties":
        x = np.round(x * 2) / 2
    if case == "zeros":
        x = np.where(rs.rand(r, t) < 0.5, 0.0, -0.0).astype(np.float32)
        x[:, ::7] = rs.randn(r, len(range(0, t, 7)))
    cand = np.arange(t)[None] <= (np.arange(r)[:, None] * 17 + (
        3 if case == "short" else 40))
    x = np.where(cand, x, -np.inf).astype(np.float32)
    # (-0.0 counts as 0.0: the lower position wins between them)
    vals, idx = jax.lax.top_k(jnp.asarray(x + 0.0), k)
    want = np.zeros((r, t), bool)
    for i in range(r):
        want[i, np.asarray(idx[i])[np.asarray(vals[i]) > -np.inf]] = True
    got = np.asarray(jax.jit(dsa.top_k_mask, static_argnums=1)(
        jnp.asarray(x), k))
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(cand.sum(-1), k)).all()
    # told where the candidates end, the passes visit an eighth, a
    # quarter, a half or the whole width (or none): the same set
    told = jax.jit(lambda sc, live: dsa.top_k_mask(sc, k, live=live))
    for live in (t, 60, 30, 12):
        cut = np.where(np.arange(t)[None] < live, x, -np.inf).astype(
            np.float32)
        np.testing.assert_array_equal(
            np.asarray(told(jnp.asarray(cut), live)),
            np.asarray(dsa.top_k_mask(jnp.asarray(cut), k)))
    at, chosen = dsa.top_k_rows(jnp.asarray(x), k)
    rows = np.zeros((r, t), bool)
    for i in range(r):
        rows[i, np.asarray(at[i])[np.asarray(chosen[i])]] = True
    assert (rows == want).all()


# ---- (c) attention over the selected set, and over the window -----------

def _latent_case(seed=1, t=64, h=4, rank=32, nope=16, pe=8, vd=16, nb=6,
                 bs=16):
    rs = np.random.RandomState(seed)
    row = mla.latent_row(rank, pe)
    lat = np.zeros((t, row), np.float32)
    lat[:, :rank + pe] = rs.randn(t, rank + pe)
    q = jnp.asarray(rs.randn(t, h, nope + pe), jnp.float32)
    w_kvb = jnp.asarray(rs.randn(rank, h, nope + vd) * 0.2, jnp.float32)
    table = np.asarray(rs.permutation(np.arange(1, 1 + nb)), np.int32)
    pool = jnp.asarray(rs.randn(1 + nb, bs, row), jnp.float32) * 50.0
    pool = pool.at[table[:t // bs]].set(lat.reshape(t // bs, bs, row))
    return jnp.asarray(lat), q, w_kvb, table, pool, (rank, nope, pe, vd)


def _plain_attention(lat, q, w_kvb, allowed, dims):
    rank, nope, pe, vd = dims
    kv = jnp.einsum("sc,chd->shd", lat[:, :rank], w_kvb,
                    precision="highest")
    s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope],
                    precision="highest")
         + jnp.einsum("qhd,kd->hqk", q[..., nope:], lat[:, rank:rank + pe],
                      precision="highest")) * (nope + pe) ** -0.5
    p = jax.nn.softmax(jnp.where(allowed[None], s, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, kv[..., nope:],
                      precision="highest")


def test_dense_under_the_mask_and_gathered_rows_are_one_attention():
    """The selection's two forms: a chunk's dense tiles under the mask
    (``mla_masked_prefill_attention``) and one absorbed query over its
    gathered rows (``mla_gathered_attention``) both give plain attention
    over the selected set."""
    lat, q, w_kvb, table, pool, dims = _latent_case()
    rank, nope, pe, vd = dims
    rs = np.random.RandomState(5)
    allowed = (rs.rand(64, 96) < 0.3) & (
        np.arange(96)[None] <= np.arange(64)[:, None])
    allowed[np.arange(64), np.arange(64)] = True
    want = _plain_attention(lat, q, w_kvb, jnp.asarray(allowed[:, :64]),
                            dims)
    got = mla.mla_masked_prefill_attention(
        q[32:], pool, table, 32, w_kvb, jnp.asarray(allowed[32:]),
        rank=rank, nope=nope, pe=pe, v_dim=vd, scale=(nope + pe) ** -0.5,
        key_tile=16)
    np.testing.assert_allclose(got, want[32:], rtol=2e-5, atol=2e-5)
    # one row a slot, absorbed, over the rows gathered for it
    pos = np.array([63, 37], np.int32)
    k = 24
    at = np.zeros((2, k), np.int32)
    chosen = np.zeros((2, k), bool)
    for i, p in enumerate(pos):
        cols = np.flatnonzero(allowed[p])[:k]
        at[i, :len(cols)], chosen[i, :len(cols)] = cols, True
    sub = np.zeros((2, 64), bool)
    for i in range(2):
        sub[i, at[i][chosen[i]]] = True
    want = np.stack([np.asarray(_plain_attention(
        lat, q[p:p + 1], w_kvb, jnp.asarray(sub[i:i + 1]), dims))[0]
        for i, p in enumerate(pos)])
    qs = q[pos] * (nope + pe) ** -0.5
    q_abs = jnp.concatenate(
        [jnp.einsum("shd,chd->shc", qs[..., :nope], w_kvb[..., :nope],
                    precision="highest"), qs[..., nope:],
         jnp.zeros((2, 4, pool.shape[-1] - rank - pe))], axis=-1)
    ctx = mla.mla_gathered_attention(
        q_abs, pool, block_tables=np.stack([table, table]),
        positions=jnp.asarray(at), chosen=jnp.asarray(chosen), rank=rank)
    got = jnp.einsum("shc,chd->shd", ctx, w_kvb[..., nope:],
                     precision="highest")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("start", [0, 512, 1024])
def test_selected_attention_kernel_is_the_dense_tile_loop(start, dtype):
    """``dsa_selected_attn`` (interpreted here) against the XLA tile loop
    under the same mask, through a shuffled block table: a chunk of 512
    rows at its first, second and third position (tiles past the chunk's
    own are neither fetched nor computed), rows that see nothing in the
    first tile, float32 and bfloat16 pools."""
    rs = np.random.RandomState(start)
    t, h, rank, nope, pe, vd, bs, nbp = 512, 2, 128, 128, 64, 128, 128, 16
    pool = jnp.asarray(rs.randn(1 + nbp, bs, mla.latent_row(rank, pe)) * 0.5,
                       dtype)
    table = np.asarray(rs.permutation(np.arange(1, 1 + nbp)), np.int32)
    q = jnp.asarray(rs.randn(t, h, nope + pe), jnp.float32)
    w_kvb = jnp.asarray(rs.randn(rank, h, nope + vd) * 0.1, dtype)
    width = nbp * bs
    assert mla.selected_tile_friendly(t, bs, rank, nope, pe, vd, nbp, width,
                                      tile=512)
    assert not mla.selected_tile_friendly(t, bs, rank, nope, pe, vd, nbp,
                                          width)      # a step of 1,024
    allowed = (rs.rand(t, width) < 0.2) & (
        np.arange(width)[None] <= (start + np.arange(t))[:, None])
    allowed[np.arange(t), start + np.arange(t)] = True
    if start:
        allowed[:t // 2, :512] = False
    kw = dict(rank=rank, nope=nope, pe=pe, v_dim=vd,
              scale=(nope + pe) ** -0.5, key_tile=512)
    want = mla.mla_masked_prefill_attention(
        q, pool, table, start, w_kvb, jnp.asarray(allowed), impl="xla", **kw)
    got = mla.mla_masked_prefill_attention(
        q, pool, table, start, w_kvb, jnp.asarray(allowed), impl="pallas",
        **kw)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    with pytest.raises(ValueError, match="whole 512-row tiles"):
        mla.mla_masked_prefill_attention(
            q[:64], pool, table, 0, w_kvb, jnp.asarray(allowed[:64]),
            impl="pallas", **kw)


@pytest.mark.parametrize("window", [9, 17, 12])
def test_window_forwards_see_the_last_rows_only(window):
    """A chunk against the ring the chunks before left, and one token
    over the ring, are plain attention under the band ``t - window < s
    <= t``; rows older than the window, and a ring full of another
    request's rows, change nothing."""
    lat, q, w_kvb, _, _, dims = _latent_case(seed=2, t=96)
    rank, nope, pe, vd = dims
    rows = mla.ring_rows(window)
    pos = np.arange(96)
    band = (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None] - window)
    want = np.asarray(_plain_attention(lat, q, w_kvb, jnp.asarray(band),
                                       dims))
    ring = jnp.full((rows, lat.shape[1]), 1e3, jnp.float32)   # stale rows
    kw = dict(window=window, rank=rank, nope=nope, pe=pe, v_dim=vd,
              scale=(nope + pe) ** -0.5)
    got = []
    for start, n in ((0, 32), (32, 32), (64, 20)):
        got.append(mla.mla_window_prefill_attention(
            q[start:start + 32], lat[start:start + 32], ring, start, w_kvb,
            **kw)[:n])
        ring = mla.ring_after_chunk(ring, lat[start:start + 32], start, n)
    np.testing.assert_allclose(np.concatenate(got), want[:84], rtol=2e-5,
                               atol=2e-5)
    held = np.asarray(mla.ring_positions(83, rows))
    assert sorted(held) == list(range(84 - rows, 84))
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(lat)[held])
    # one token a step from there, two slots (one of them a stale ring)
    rings = jnp.stack([ring, jnp.full_like(ring, 1e3)])
    for t in range(84, 96):
        rings = rings.at[0, t % rows].set(lat[t])
        rings = rings.at[1, 0].set(lat[0])
        p2 = np.array([t, 0], np.int32)
        qs = q[p2] * (nope + pe) ** -0.5
        q_abs = jnp.concatenate(
            [jnp.einsum("shd,chd->shc", qs[..., :nope], w_kvb[..., :nope],
                        precision="highest"), qs[..., nope:],
             jnp.zeros((2, 4, lat.shape[1] - rank - pe))], axis=-1)
        ctx = mla.mla_window_decode_attention(
            q_abs, rings, jnp.asarray(p2), window=window, rank=rank)
        out = jnp.einsum("shc,chd->shd", ctx, w_kvb[..., nope:],
                         precision="highest")
        np.testing.assert_allclose(out[0], want[t], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(out[1], want[0], rtol=2e-5, atol=2e-5)


# ---- (d) chunked prefill, then decode, through the three caches ---------

def _state(model, fill=0.0):
    specs = model.state_specs(slots=SLOTS, num_blocks=1 + SLOTS * NB,
                              block_size=BS)
    return {k: jnp.full(v["shape"], fill, v["dtype"])
            for k, v in specs.items()}


def _serve_by_hand(model, params, toks, p, slot=1, state=None, jit=True):
    """Chunked prefill of ``toks[:p]`` then one decode step a further
    token, through the state: logits at every position, and the state."""
    state = _state(model) if state is None else state
    table = np.zeros((SLOTS, NB), np.int32)
    table[slot] = 1 + slot * NB + np.arange(NB)
    wrap = jax.jit if jit else (lambda f: f)
    fn = wrap(lambda st, ids, n, start, cb: model.prefill_chunk(
        params, st, ids, n, start, slot, table[slot], cb, with_logits=True))
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
    step = wrap(lambda st, tok, pos, alive: model.decode_step(
        params, st, table, tok, pos, alive, attention="xla",
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
    window (9), the top-k (16) and a block (16). Tolerance 3e-5 on logits
    of magnitude ~2: float32 rounding through five layers in another
    order."""
    model, params = f32
    toks = np.random.RandomState(p).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, first, state = _serve_by_hand(model, params, toks, p)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert first == int(np.argmax(want[p - 1]))
    # the other slots' rings were never touched
    assert not np.asarray(state["cache_window"][:, 0]).any()
    assert not np.asarray(state["cache_window"][:, 2]).any()


def test_chunked_prefill_then_decode_in_bfloat16_is_near_it():
    """bfloat16 storage and operands against the float32 reference on the
    same (bfloat16-rounded) weights: the served argmax lies under the
    reference's best logit by under 0.15 on average (0.084 read; logits
    spread ~2; at these widths one row of 16 or one expert of 8 chosen
    otherwise moves a logit far, which is why the rehearsal runs in
    float32)."""
    model, params = build("bfloat16")
    toks = np.random.RandomState(3).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, _, _ = _serve_by_hand(model, params, toks, 70)
    gap = want.max(-1) - want[np.arange(90), got.argmax(-1)]
    assert gap.mean() < 0.15 and np.abs(got - want).mean() < 0.15


def test_the_selected_set_is_the_references(f32, monkeypatch):
    """Every full-attention layer selects the reference's rows, in the
    chunk (a mask) and in the one-token step (positions), at float32."""
    model, params = f32
    toks = np.random.RandomState(21).randint(0, 384, 90).astype(np.int32)
    masks, steps = [], []
    real, real_rows = model._select, model._select_rows
    monkeypatch.setattr(model, "_select", lambda sc, k, live: masks.append(
        np.asarray(real(sc, k, live))) or jnp.asarray(masks[-1]))

    def rows(sc, k):
        at, chosen = real_rows(sc, k)
        steps.append((np.asarray(at)[1], np.asarray(chosen)[1]))
        return at, chosen

    monkeypatch.setattr(model, "_select_rows", rows)
    _serve_by_hand(model, params, toks, 70, jit=False)
    for layer in (0, 1):
        want = np.asarray(ref.selection(CFG, params, jnp.asarray(toks),
                                        layer))
        for c, start in enumerate((0, 32, 64)):
            n = min(32, 70 - start)
            got = masks[2 * c + layer][:n, :90]
            assert (got == want[start:start + n]).all(), (layer, start)
            assert (got.sum(-1) == np.minimum(
                start + 1 + np.arange(n), 16)).all()
        for t in range(70, 90):
            at, chosen = steps[2 * (t - 70) + layer]
            assert sorted(at[chosen]) == list(np.flatnonzero(want[t]))


def test_a_reused_slot_and_reused_blocks_never_reach_the_next_request(f32):
    """A request that takes a slot whose ring, and blocks whose latent
    rows and index keys, another request left (here: every array full of
    large values) is served the reference's logits all the same: a row of
    any of the three caches is written before it is read."""
    model, params = f32
    toks = np.random.RandomState(13).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    got, _, _ = _serve_by_hand(model, params, toks, 70,
                               state=_state(model, fill=30.0))
    np.testing.assert_allclose(got, want, atol=3e-5)
    # and a first request's own rows do not leak into the second's
    _, _, dirty = _serve_by_hand(model, params, toks[::-1].copy(), 80)
    got, _, _ = _serve_by_hand(model, params, toks, 70, state=dirty)
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("fault", ["selection_dropped", "window_not_applied",
                                   "rope_left_off_k_pe",
                                   "index_keys_stale"])
def test_each_planted_fault_shows_in_the_logits(f32, monkeypatch, fault):
    """What ``benchmark/planted_dsa.py`` plants in the cell, by hand at
    test widths: each moves the logits far beyond rounding."""
    from benchmark import planted_dsa
    model, params = f32
    toks = np.random.RandomState(9).randint(0, 384, 90).astype(np.int32)
    want = np.asarray(ref.logits(CFG, params, jnp.asarray(toks)))
    broken, _ = build("float32")
    planted_dsa.FAULTS[fault](model=broken)
    got, _, _ = _serve_by_hand(broken, params, toks, 70,
                               state=_state(model, fill=0.5))
    assert np.abs(got[40:] - want[40:]).max() > 1e-2
    sound, _, _ = _serve_by_hand(model, params, toks, 70,
                                 state=_state(model, fill=0.5))
    np.testing.assert_allclose(sound, want, atol=3e-5)


# ---- (e) the artifact and the engine ------------------------------------

@pytest.fixture(scope="module")
def artifact(f32, tmp_path_factory):
    model, params = f32
    out = str(tmp_path_factory.mktemp("dots3_tiny"))
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


def test_artifact_round_trip_records_its_three_caches(artifact, f32):
    model, _ = f32
    meta = json.load(open(os.path.join(artifact, "export.json")))
    sm = meta["stepwise"]
    assert meta["model"] == "dots3_note"
    assert not os.path.exists(os.path.join(artifact, "prefill.stablehlo"))
    assert not os.path.exists(os.path.join(artifact, "model.stablehlo"))
    assert sm["prefill_chunk"] == CHUNK and sm["paged"]
    st = sm["state"]
    assert st["mixers"] == ["mla_sparse", "mla_sparse", "mla_window",
                            "mla_window", "mla_window"]
    assert st["ffns"] == ["dense", "moe", "moe", "moe", "moe"]
    assert (st["index_topk"], st["window"]) == (16, 9)
    assert st["specs"] == json.loads(json.dumps(model.state_specs(
        slots=SLOTS, num_blocks=sm["num_blocks"], block_size=BS)))
    assert sm["pool_shape"] == st["specs"]["cache_latent"]["shape"]
    # a block costs its latent rows and its index keys, both full layers
    assert sm["block_bytes"] == 2 * BS * (128 + 16) * 4
    sw = serving.load_stepwise(artifact)
    pool = sw.make_pool()
    assert {k: list(v.shape) for k, v in pool.items()} == {
        k: v["shape"] for k, v in st["specs"].items()}
    # zeroing a slot touches that slot's ring and nothing else
    z = sw.zero_slot({k: jnp.ones_like(v) for k, v in pool.items()}, 1)
    assert not np.asarray(z["cache_window"][:, 1]).any()
    assert np.asarray(z["cache_window"][:, 0]).all()
    assert np.asarray(z["cache_latent"]).all()
    assert np.asarray(z["cache_index"]).all()
    with pytest.raises(ValueError, match="scheduler"):
        serving.load_servable(artifact)({"input_ids": np.zeros((1, 4))})


def test_engine_generates_what_the_reference_generates(artifact, f32):
    """Requests of unlike lengths (inside one chunk, over three, on a
    chunk boundary) through chunked prefill and the shared one-token
    step, more requests than slots so that slots and blocks are reused:
    each gives the reference's greedy tokens, as it does alone; the spans'
    counts add up."""
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
        assert st["state"]["mixers"][2] == "mla_window"
        assert st["state"]["bytes"] == {
            "cache_latent": int(np.prod(st["pool_shape"])) * 4,
            "cache_index": 2 * st["pool_shape"][1] * BS * 16 * 4,
            "cache_window": 3 * SLOTS * 16 * 128 * 4}
        assert st["latent_pool_bytes"] == st["state"]["bytes"][
            "cache_latent"]
        assert st["index_pool_bytes"] == st["state"]["bytes"]["cache_index"]
        assert st["window_cache_bytes"] == st["state_bytes"] == st[
            "state"]["bytes"]["cache_window"]
        # every dispatched row's context, and min(context, 16) of it,
        # over the two full layers: prompts row by row, then each
        # generated token but the last (it is never fed back)
        ctx = [np.arange(1, n + k) for n, k in zip(lens, new)]
        assert st["dsa_context_rows"] == 2 * sum(int(c.sum()) for c in ctx)
        assert st["dsa_selected_rows"] == 2 * sum(
            int(np.minimum(c, 16).sum()) for c in ctx)
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


def test_spans_say_what_was_selected(artifact):
    """``prefill_chunk`` and ``decode_step`` spans of a selecting artifact
    carry ``index_bytes``, ``selected_rows``, ``context_rows``,
    ``kv_bytes`` (the selected rows as stored) and ``window_bytes``."""
    eng = GenerationEngine(serving.load_stepwise(artifact))
    chunk = eng._describe_selection(32 + 1 + np.arange(20), 52)
    assert chunk == {
        "index_bytes": 52 * 2 * 16 * 4,
        "context_rows": 2 * sum(range(33, 53)), "selected_rows": 2 * 20 * 16,
        "kv_bytes": 2 * 20 * 16 * 128 * 4,
        "window_bytes": 20 * 9 * 3 * 128 * 4}
    feats = {"alive": np.array([1, 0, 1]), "pos": np.array([4, 0, 40]),
             "tok": np.zeros(3, np.int32)}
    step = eng._describe_state_decode(feats)
    assert step["slots"] == 2
    assert step["context_rows"] == 2 * (5 + 41)
    assert step["selected_rows"] == 2 * (5 + 16)
    assert step["kv_bytes"] == 2 * (5 + 16) * 128 * 4
    assert step["index_bytes"] == (5 + 41) * 2 * 16 * 4
    assert step["window_bytes"] == (5 + 9) * 3 * 128 * 4
    eng.close()


# ---- (e') a chunk whose expert layers run over a bound of the pairs ------

WIDE_CHUNK = 128        # x top-2 of 16 experts, 2 held: 256 pairs, bound 128


def _wide(all_held: bool = False):
    """The tiny layers with 16 experts of which this chip holds 2 (1 in
    8, the cell's share), so that a 128-row chunk's expert layers run
    over ``pair_bound`` = 128 of their 256 pairs; ``all_held``: a
    selection bias that sends EVERY pick to the two held experts (256
    held pairs: every layer falls back to the whole width)."""
    import dataclasses
    from distributed_tensorflow_example_tpu.models.decoder import (
        BlockDecoder, DecoderBlockConfig)
    model = BlockDecoder(dataclasses.replace(
        DecoderBlockConfig.dots3_note_tiny(), experts=16, experts_held=2),
        dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.key(38))
    if all_held:
        for lp in params["layers"].values():
            if "moe" in lp:
                lp["moe"]["router_bias"] = lp["moe"]["router_bias"].at[
                    :2].add(100.0)
    return model, params


def _wide_chunk(model, params, toks):
    """One 128-row chunk of ``toks`` through ``prefill_chunk``: its output
    (logits at every row)."""
    specs = model.state_specs(slots=SLOTS, num_blocks=1 + SLOTS * NB,
                              block_size=BS)
    state = {k: jnp.zeros(v["shape"], v["dtype"]) for k, v in specs.items()}
    blocks = 1 + np.arange(NB, dtype=np.int32)
    ids = np.zeros((1, WIDE_CHUNK), np.int32)
    ids[0, :len(toks)] = toks
    return jax.jit(lambda st, ids: model.prefill_chunk(
        params, st, ids, len(toks), 0, 0, blocks, blocks,
        with_logits=True))(state, ids)


@pytest.mark.parametrize("all_held", [False, True],
                         ids=["bounded", "overflow"])
def test_a_bounded_chunk_is_the_whole_width_chunk(all_held, monkeypatch):
    """The chunk program of a chip that holds 1 in 8 experts: the same
    logits with the bound and without it, and ``moe_whole`` says how many
    of its four expert layers passed the bound (none under seeded
    routing; all four when every pick is a held expert). A program
    without a bound returns no such scalar (Kimi's, the cell's decode
    step, this file's other chunks)."""
    from distributed_tensorflow_example_tpu.models import decoder as dec_mod
    from distributed_tensorflow_example_tpu.ops import moe as moe_mod
    model, params = _wide(all_held)
    toks = np.random.RandomState(3).randint(0, 384, 100)
    got = _wide_chunk(model, params, toks)
    assert int(got["moe_whole"]) == (4 if all_held else 0)
    for mod in (dec_mod, moe_mod):
        monkeypatch.setattr(mod, "pair_bound", lambda pairs, *_: pairs)
    want = _wide_chunk(model, params, toks)
    assert "moe_whole" not in want
    np.testing.assert_allclose(got["logits"][:100], want["logits"][:100],
                               rtol=1e-5, atol=1e-5)
    assert int(got["ids"][0]) == int(want["ids"][0])
    assert int(got["expert_rows"]) == int(want["expert_rows"])


def test_engine_counts_bounded_and_whole_layers(tmp_path):
    """``export.json`` names the rows each program's expert layers were
    traced over; ``/stats`` counts, a chunk program, its expert layers
    that ran over the bound and those that fell back, from the scalar
    the program returns beside the id."""
    lens = [100, 128, 200]
    chunks = sum(-(-n // WIDE_CHUNK) for n in lens)
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, 384, n).tolist() for n in lens]
    tokens = []
    for all_held in (False, True):
        model, params = _wide(all_held)
        out = str(tmp_path / f"wide{int(all_held)}")
        serving.export_generator(
            model, params, out, ragged=True, stepwise=True, paged=True,
            slots=SLOTS, block_size=BS, prompt_len=256, max_new_tokens=8,
            prefill_chunk=WIDE_CHUNK, platforms=("cpu",))
        st = json.load(open(os.path.join(out, "export.json")))[
            "stepwise"]["state"]
        assert st["moe_rows"] == {
            "prefill_chunk": {"pairs": 256, "bound": 128},
            "decode": {"pairs": 2 * SLOTS, "bound": 2 * SLOTS}}
        assert set(st["moe_tiles"]["prefill_chunk"].values()) == {"xla"}
        eng = GenerationEngine(serving.load_stepwise(out)).start()
        try:
            tokens.append([eng.submit(p, max_new=4).result(timeout=300)
                           for p in prompts])
            stats = eng.stats()
        finally:
            eng.close()
        assert stats["prefill_chunks"] == chunks
        assert (stats["moe_bounded_layers"], stats["moe_whole_layers"]) == (
            (0, 4 * chunks) if all_held else (4 * chunks, 0))
    assert all(len(t) == 4 for run in tokens for t in run)


def test_a_program_without_a_bound_counts_nothing(artifact):
    """This file's artifact (64 pairs a chunk: no bound): its chunk
    program returns no ``moe_whole`` and both counters stay at zero."""
    meta = json.load(open(os.path.join(artifact, "export.json")))
    assert meta["stepwise"]["state"]["moe_rows"] == {
        "prefill_chunk": {"pairs": 2 * CHUNK, "bound": 2 * CHUNK},
        "decode": {"pairs": 2 * SLOTS, "bound": 2 * SLOTS}}
    eng = GenerationEngine(serving.load_stepwise(artifact)).start()
    try:
        assert len(eng.submit(list(range(1, 41)), max_new=3).result(
            timeout=300)) == 3
        st = eng.stats()
    finally:
        eng.close()
    assert st["prefill_chunks"] == 2
    assert (st["moe_bounded_layers"], st["moe_whole_layers"]) == (0, 0)


# ---- (f) the other served programs are the parent's ---------------------

#: sha256 (16 hex digits) of each program exported at the parent commit
#: (10211e5), weights baked from a fixed key: the same bytes for the same
#: inputs. GPT-2's and SDAR's are tests/test_kimi_decoder.py's, still.
PARENT_PROGRAMS = {
    "kimi/decode.stablehlo": "55c04e6d7ecbdd2e",
    "kimi/prefill_chunk.stablehlo": "29a037c9933aa30f",
}


def test_kimi_programs_are_the_parents(tmp_path):
    """What this PR added to the block description, ``ops/mla.py``, the
    exporter and the engine leaves Kimi's two programs as they were."""
    import test_kimi_decoder as kimi
    model, _ = kimi.build("float32")
    out = str(tmp_path / "kimi")
    serving.export_generator(
        model, model.init(jax.random.key(0)), out, ragged=True,
        stepwise=True, paged=True, slots=kimi.SLOTS, block_size=kimi.BS,
        prompt_len=kimi.PROMPT, max_new_tokens=kimi.NEW,
        prefill_chunk=kimi.CHUNK, platforms=("cpu",))
    got = {f"kimi/{f}": hashlib.sha256(kimi._program_text(
               os.path.join(out, f)).encode()).hexdigest()[:16]
           for f in sorted(os.listdir(out)) if f.endswith(".stablehlo")}
    assert got == PARENT_PROGRAMS
    meta = json.load(open(os.path.join(out, "export.json")))["stepwise"]
    # one latent layer of 32 + 8 values padded to 128, float32
    assert meta["block_bytes"] == kimi.BS * 128 * 4

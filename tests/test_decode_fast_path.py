"""Decode fast path: stacked-scan step, Pallas cache-slab attention,
multi-token dispatch, int8 weight rows — all against the ``"loop"``
reference path (tier-1, CPU; Pallas kernels in interpret mode).

The load-bearing contract: the fast path is a pure re-expression of the
decode computation — greedy token streams must match the reference
EXACTLY across every generate knob (ragged prompts, EOS early-stop,
sampling), because the bench gate publishes fast-path numbers against a
baseline recorded on the reference semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model
from distributed_tensorflow_example_tpu.models.gpt import GPT, GPTConfig
from distributed_tensorflow_example_tpu.ops.pallas.decode_attention import (
    decode_attention, tile_friendly, xla_decode_attention)


def _model():
    return get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))


def _prompt(m, b=3, s=9, seed=2):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randint(0, m.cfg.vocab_size, (b, s),
                                  dtype=np.int32))


# ---------------------------------------------------------------------------
# stacked-scan step vs the reference loop step
# ---------------------------------------------------------------------------

def test_stacked_step_matches_loop_step_logits_and_caches():
    """One decode step: the lax.scan-over-stacked-params body must
    reproduce the per-layer loop's logits AND cache writes."""
    m = _model()
    params = m.init(jax.random.key(3))
    ids = _prompt(m)
    total = 9 + 4
    _, caches = m._prefill(params, ids, total)
    tok = jnp.asarray([5, 7, 11], jnp.int32)
    pos = jnp.int32(9)
    want_logits, want_caches = m._decode_step(params, caches, tok, pos)
    stacked = m.stack_decode_params(params)
    got_logits, got_caches = m._decode_step_stacked(
        params, stacked, m._stack_caches(caches), tok, pos)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits),
                               rtol=1e-5, atol=1e-5)
    for i in range(m.cfg.layers):
        for n in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(got_caches[n][i]),
                np.asarray(want_caches[f"layer_{i}"][n]),
                rtol=1e-5, atol=1e-5, err_msg=f"layer {i} {n}")


@pytest.mark.parametrize("knobs", [
    dict(),                                           # plain greedy
    dict(tokens_per_dispatch=4),                      # K-token unroll
    dict(eos="mid"),                                  # early-stop path
    dict(ragged=True),                                # right-packed pads
    dict(temperature=1.0),                            # sampled
    dict(temperature=0.9, top_k=7, tokens_per_dispatch=3),
])
def test_stacked_generate_matches_loop(knobs):
    """generate(decode_impl="stacked") returns exactly the tokens of
    decode_impl="loop" under every knob combination."""
    knobs = dict(knobs)
    m = _model()
    params = m.init(jax.random.key(4))
    ids = _prompt(m, seed=3)
    kw: dict = {}
    if knobs.pop("ragged", False):
        mask = np.zeros((3, 9), np.int32)
        for i, n in enumerate((9, 4, 1)):
            mask[i, :n] = 1
        kw["prompt_mask"] = jnp.asarray(mask)
    if knobs.pop("eos", None):
        free = np.asarray(m.generate(params, ids, 8, decode_impl="loop"))
        kw["eos_id"] = int(free[0, 3])
        kw["pad_id"] = -1
    if knobs.get("temperature"):
        kw["rng"] = jax.random.key(11)
    kw.update(knobs)
    k = kw.pop("tokens_per_dispatch", 1)
    want = m.generate(params, ids, 8, decode_impl="loop", **kw)
    got = m.generate(params, ids, 8, decode_impl="stacked",
                     tokens_per_dispatch=k, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tokens_per_dispatch_larger_than_max_new_clamps():
    m = _model()
    params = m.init(jax.random.key(0))
    ids = _prompt(m, b=1, s=4)
    want = m.generate(params, ids, 3)
    got = m.generate(params, ids, 3, tokens_per_dispatch=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert m.generate(params, ids, 1, tokens_per_dispatch=4).shape == (1, 1)


def test_default_generate_is_the_stacked_path():
    """The fast path IS the default: generate() with no knobs equals
    both impls (guards against the default silently flipping)."""
    m = _model()
    params = m.init(jax.random.key(5))
    ids = _prompt(m, seed=5)
    default = m.generate(params, ids, 6)
    np.testing.assert_array_equal(
        np.asarray(default),
        np.asarray(m.generate(params, ids, 6, decode_impl="stacked")))
    np.testing.assert_array_equal(
        np.asarray(default),
        np.asarray(m.generate(params, ids, 6, decode_impl="loop")))


# ---------------------------------------------------------------------------
# the Pallas single-query cache-slab attention kernel
# ---------------------------------------------------------------------------

def test_pallas_decode_attention_matches_xla_reference():
    """Kernel (interpret mode on CPU) vs the XLA reference at a
    tile-friendly shape, with a ragged pad and a mid-slab pos."""
    rs = np.random.RandomState(0)
    b, t, h, d = 2, 128, 4, 64
    q = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, t, h, d).astype(np.float32))
    pos, pad = jnp.int32(90), jnp.asarray([0, 37], jnp.int32)
    got = decode_attention(q, k, v, pos=pos, pad=pad, impl="pallas")
    want = xla_decode_attention(q, k, v, pos=pos, pad=pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pallas_decode_attention_bf16_cache():
    """The gate's actual dtype: bf16 q/k/v, f32 softmax inside."""
    rs = np.random.RandomState(1)
    b, t, h, d = 2, 128, 2, 64
    mk = lambda *s: jnp.asarray(rs.randn(*s).astype(np.float32) * 0.5,
                                jnp.bfloat16)
    q, k, v = mk(b, h, d), mk(b, t, h, d), mk(b, t, h, d)
    pos, pad = jnp.int32(127), jnp.asarray([3, 0], jnp.int32)
    got = decode_attention(q, k, v, pos=pos, pad=pad, impl="pallas")
    want = xla_decode_attention(q, k, v, pos=pos, pad=pad)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_decode_attention_masking_ignores_dead_slots():
    """Garbage beyond pos and below pad must not change the context —
    the pad/pos mask is fused into the kernel."""
    rs = np.random.RandomState(2)
    b, t, h, d = 2, 128, 2, 64
    q = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
    k = rs.randn(b, t, h, d).astype(np.float32)
    v = rs.randn(b, t, h, d).astype(np.float32)
    pos, pad = jnp.int32(60), jnp.asarray([5, 0], jnp.int32)
    base = decode_attention(jnp.asarray(q), jnp.asarray(k),
                            jnp.asarray(v), pos=pos, pad=pad,
                            impl="pallas")
    k2, v2 = k.copy(), v.copy()
    k2[:, 61:], v2[:, 61:] = 99.0, -99.0       # beyond pos
    k2[0, :5], v2[0, :5] = -99.0, 99.0         # below pad (row 0)
    poisoned = decode_attention(jnp.asarray(q), jnp.asarray(k2),
                                jnp.asarray(v2), pos=pos, pad=pad,
                                impl="pallas")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


def test_tile_friendly_gate_and_fallback():
    assert tile_friendly(128, 12, 64) and tile_friendly(256, 3, 128)
    assert not tile_friendly(120, 12, 64)  # T not a lane multiple
    assert not tile_friendly(128, 4, 32)   # head dim not lane-tileable
    assert not tile_friendly(128, 3, 64)   # D=64 pairs heads: H even
    # auto at an unfriendly shape rides the XLA path (no error)
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 2, 32).astype(np.float32))
    kv = jnp.asarray(rs.randn(1, 24, 2, 32).astype(np.float32))
    pad = jnp.zeros((1,), jnp.int32)
    out = decode_attention(q, kv, kv, pos=jnp.int32(7), pad=pad,
                           impl="auto")
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(xla_decode_attention(q, kv, kv, pos=jnp.int32(7),
                                        pad=pad)), rtol=1e-6)
    with pytest.raises(ValueError, match="T % 128"):
        decode_attention(q, kv, kv, pos=jnp.int32(7), pad=pad,
                         impl="pallas")
    with pytest.raises(ValueError, match="impl"):
        decode_attention(q, kv, kv, pos=jnp.int32(7), pad=pad,
                         impl="mosaic")


def test_pallas_generate_end_to_end_matches_xla():
    """Forced-kernel generate at a tile-friendly config (D=64,
    total=128): the full prefill+decode program with the Pallas
    attention inside the scan body, greedy-equal to the XLA path."""
    cfg = GPTConfig(vocab_size=256, hidden=128, layers=2, heads=2,
                    intermediate=256, max_len=256, dropout=0.0)
    m = GPT(cfg)
    params = m.init(jax.random.key(0))
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 120),
                                 dtype=np.int32))
    want = m.generate(params, ids, 8, decode_attention="xla")
    got = m.generate(params, ids, 8, decode_attention="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# int8 weight-quantized decode (the lever-table comparison row)
# ---------------------------------------------------------------------------

def test_int8_stack_quantization_error_bounded():
    """Symmetric per-output-channel int8: |w - dequant(w)| <= scale/2
    everywhere (round-to-nearest), scale = channel max / 127."""
    m = _model()
    params = m.init(jax.random.key(6))
    stacked = m.stack_decode_params(params, weight_quant="int8")
    for name in ("qkv", "o", "ffn_in", "ffn_out"):
        dp = stacked[name]
        assert dp["kernel_q"].dtype == jnp.int8
        deq = np.asarray(dp["kernel_q"], np.float32) * np.asarray(
            dp["scale"])
        # reconstruct the float stack the quantizer saw
        ref = np.asarray(m.stack_decode_params(params)[name]["kernel"],
                         np.float32)
        err = np.abs(deq - ref)
        assert (err <= np.asarray(dp["scale"]) / 2 + 1e-7).all(), \
            f"{name}: max err {err.max()}"


def test_int8_decode_generates_and_tracks_greedy():
    """The int8 row must run end to end and stay CLOSE to the bf16
    greedy stream (it is lossy by contract, not by accident — on this
    tiny model the first few greedy tokens should survive 8-bit
    weights)."""
    m = _model()
    params = m.init(jax.random.key(7))
    ids = _prompt(m, seed=7)
    full = np.asarray(m.generate(params, ids, 6))
    q8 = np.asarray(m.generate(params, ids, 6, weight_quant="int8"))
    assert q8.shape == full.shape and q8.dtype == full.dtype
    # the very first emitted token comes from the UNquantized prefill
    # (prefill runs the full-precision forward), so it must match
    np.testing.assert_array_equal(q8[:, 0], full[:, 0])


# ---------------------------------------------------------------------------
# knob validation
# ---------------------------------------------------------------------------

def test_fast_path_knob_validation():
    m = _model()
    params = m.init(jax.random.key(0))
    ids = _prompt(m, b=1, s=4)
    with pytest.raises(ValueError, match="decode_impl"):
        m.generate(params, ids, 2, decode_impl="fused")
    with pytest.raises(ValueError, match="tokens_per_dispatch"):
        m.generate(params, ids, 2, tokens_per_dispatch=0)
    with pytest.raises(ValueError, match="eos_id"):
        m.generate(params, ids, 2, tokens_per_dispatch=2, eos_id=3)
    with pytest.raises(ValueError, match="stacked"):
        m.generate(params, ids, 2, decode_impl="loop",
                   weight_quant="int8")
    with pytest.raises(ValueError, match="decode_attention"):
        m.generate(params, ids, 2, decode_impl="loop",
                   decode_attention="pallas")
    with pytest.raises(ValueError, match="weight_quant"):
        m.stack_decode_params(params, weight_quant="int4")
    with pytest.raises(ValueError, match="decode_attention_impl"):
        GPT(GPTConfig.tiny(), decode_attention_impl="fused")


# ---------------------------------------------------------------------------
# export wiring
# ---------------------------------------------------------------------------

def test_export_generator_records_fast_path_metadata(tmp_path):
    """The serving artifact rides the fast path and says so: metadata
    carries decode_impl/tokens_per_dispatch (and prng_impl when
    sampling), and the servable reproduces direct generate output."""
    from distributed_tensorflow_example_tpu.serving import (
        export_generator, load_servable)
    m = _model()
    params = m.init(jax.random.key(8))
    d = str(tmp_path / "gen")
    export_generator(m, params, d, prompt_len=6, max_new_tokens=4,
                     batch_size=2, tokens_per_dispatch=2)
    sv = load_servable(d)
    assert sv.meta["decode_impl"] == "stacked"
    assert sv.meta["tokens_per_dispatch"] == 2
    assert "prng_impl" not in sv.meta          # greedy: no rng input
    ids = _prompt(m, b=2, s=6, seed=9)
    want = m.generate(params, ids, 4, tokens_per_dispatch=2,
                      decode_attention="xla")
    got = sv({"input_ids": np.asarray(ids)})
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_export_generator_sampled_records_prng_impl(tmp_path):
    from distributed_tensorflow_example_tpu.serving import (
        export_generator, load_servable)
    m = _model()
    params = m.init(jax.random.key(8))
    d = str(tmp_path / "gen_sampled")
    export_generator(m, params, d, prompt_len=5, max_new_tokens=3,
                     batch_size=1, temperature=1.0)
    sv = load_servable(d)
    assert sv.meta["prng_impl"] == str(
        jax.random.key_impl(jax.random.key(0)))
    assert list(sv.input_signature["rng"]["shape"]) == list(
        np.shape(jax.random.key_data(jax.random.key(0))))


# ---------------------------------------------------------------------------
# K-token speculative verify step (round 16): one dispatch == K
# sequential decode steps, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_verify_step_matches_sequential_paged_decode_bitwise(kv):
    """``decode_verify_batched_paged`` is the batched step over
    row-expanded lanes — its per-lane logits AND its pool writes must
    equal K sequential ``decode_step_batched_paged`` dispatches of the
    same tokens EXACTLY (the byte-parity foundation the engine's
    accept rule stands on), and write-gated lanes (>= n_tok, or a dead
    row) must leave the pool untouched. Float and int8 pools
    ([L, N, Bs, H*D], carried through the step's layer scan)."""
    m = _model()
    params = m.init(jax.random.key(0))
    c = m.cfg
    slots, bs, nblocks, kk = 2, 4, 12, 3
    hd = c.hidden // c.heads
    shape = (c.layers, nblocks, bs, c.heads * hd)
    prompt = np.array([[5, 6, 7, 8, 9]], np.int32)
    scales = ({} if kv == "float32" else
              {"k_scale": jnp.zeros(shape[:3], jnp.float32),
               "v_scale": jnp.zeros(shape[:3], jnp.float32)})
    out = m.paged_prefill(params, prompt, np.ones_like(prompt),
                          jnp.zeros(shape, kv), jnp.zeros(shape, kv),
                          np.array([1, 2], np.int32), **scales)
    start = dict(zip(("k", "v", "k_scale", "v_scale"), out[1:]))
    stacked = m.stack_decode_params(params)
    bt = np.zeros((slots, 4), np.int32)
    bt[0, :3] = [1, 2, 3]
    toks = [9, 17, 23]                    # anchor + two draft tokens
    pos0 = 5

    seq_pools = start
    seq_logits = []
    for j, t in enumerate(toks):
        lg, seq_pools = m.decode_step_batched_paged(
            params, stacked, seq_pools, jnp.asarray(bt),
            jnp.array([t, 0], jnp.int32),
            jnp.array([pos0 + j, 0], jnp.int32),
            jnp.zeros((slots,), jnp.int32),
            jnp.array([1, 0], jnp.int32), decode_attention="xla")
        seq_logits.append(np.asarray(lg)[0])

    tokv = np.zeros((slots, kk), np.int32)
    tokv[0] = toks
    ver_logits, ver_pools = m.decode_verify_batched_paged(
        params, stacked, start, jnp.asarray(bt),
        jnp.asarray(tokv), jnp.array([pos0, 0], jnp.int32),
        jnp.zeros((slots,), jnp.int32), jnp.array([1, 0], jnp.int32),
        jnp.array([kk, 1], jnp.int32), decode_attention="xla")
    ver_logits = np.asarray(ver_logits)
    assert ver_logits.shape == (slots, kk, c.vocab_size)
    for j in range(kk):
        np.testing.assert_array_equal(seq_logits[j], ver_logits[0, j])
    for n in start:
        np.testing.assert_array_equal(np.asarray(seq_pools[n]),
                                      np.asarray(ver_pools[n]))

    # n_tok gating: width 1 (no drafts) must write EXACTLY what one
    # sequential step writes — the extra lanes rewrite old bytes
    one_logits, one_pools = m.decode_verify_batched_paged(
        params, stacked, start, jnp.asarray(bt),
        jnp.asarray(tokv), jnp.array([pos0, 0], jnp.int32),
        jnp.zeros((slots,), jnp.int32), jnp.array([1, 0], jnp.int32),
        jnp.array([1, 1], jnp.int32), decode_attention="xla")
    lg1, p1 = m.decode_step_batched_paged(
        params, stacked, start, jnp.asarray(bt),
        jnp.array([toks[0], 0], jnp.int32),
        jnp.array([pos0, 0], jnp.int32),
        jnp.zeros((slots,), jnp.int32),
        jnp.array([1, 0], jnp.int32), decode_attention="xla")
    np.testing.assert_array_equal(np.asarray(one_logits)[0, 0],
                                  np.asarray(lg1)[0])
    for n in start:
        np.testing.assert_array_equal(np.asarray(one_pools[n]),
                                      np.asarray(p1[n]))

"""Pallas flash attention vs reference attention (interpret mode on CPU).

Two shape regimes on purpose: the tiny-D tests (D=32, block_k=32 — not
Mosaic-tileable) exercise the silent XLA fallback boundary; the
kernel-path tests (D=64, S % 128 == 0, block_k % 128 == 0) run the REAL
kernels in interpret mode, including the round-6 lever surface
(non-default blocks, bwd_block, the fused backward) and its loud
config-validation failures.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_example_tpu.ops.attention import (
    multi_head_attention)
from distributed_tensorflow_example_tpu.ops.pallas.flash_attention import (
    attention_train_flops, flash_attention, kernel_engages)

B, S, H, D = 2, 64, 2, 32
BLK = dict(block_q=32, block_k=32)


def _qkv(seed=0):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(B, S, H, D).astype(np.float32) * 0.4)
                 for _ in range(3))


def test_forward_matches_reference():
    q, k, v = _qkv()
    want = multi_head_attention(q, k, v)
    got = flash_attention(q, k, v, **BLK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_causal():
    q, k, v = _qkv(1)
    want = multi_head_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, **BLK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_padding_mask():
    q, k, v = _qkv(2)
    mask = np.ones((B, S), np.int32)
    mask[:, 48:] = 0
    want = multi_head_attention(q, k, v,
                                mask=jnp.asarray(mask)[:, None, None, :])
    got = flash_attention(q, k, v, mask=jnp.asarray(mask), **BLK)
    np.testing.assert_allclose(np.asarray(got)[:, :48],
                               np.asarray(want)[:, :48],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q, k, v = _qkv(3)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    ref = jax.grad(loss(lambda q, k, v: multi_head_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    fl = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, **BLK)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ref, fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_grads_with_mask():
    q, k, v = _qkv(4)
    mask = np.ones((B, S), np.int32)
    mask[:, 40:] = 0
    m4 = jnp.asarray(mask)[:, None, None, :]

    # only valid rows contribute to the loss (padded-row outputs are
    # unnormalized by design)
    ref = jax.grad(lambda q, k, v: jnp.sum(multi_head_attention(
        q, k, v, mask=m4)[:, :40] ** 2), argnums=(0, 1, 2))(q, k, v)
    fl = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, mask=jnp.asarray(mask), **BLK)[:, :40] ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ref, fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_non_divisible_seq_falls_back():
    rs = np.random.RandomState(5)
    q, k, v = [jnp.asarray(rs.randn(1, 50, 2, 16).astype(np.float32))
               for _ in range(3)]
    want = multi_head_attention(q, k, v)
    got = flash_attention(q, k, v)        # 50 % 128 != 0 → xla path
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_bert_with_flash_attention_matches_xla():
    from distributed_tensorflow_example_tpu.models.bert import (Bert,
                                                                BertConfig)
    cfg = BertConfig.tiny()
    cfg.dropout = 0.0
    m_x = Bert(cfg, attention_impl="xla")
    m_f = Bert(cfg, attention_impl="flash")
    params = m_x.init(jax.random.key(0))
    batch = m_x.dummy_batch(2)
    lx, _ = m_x.loss(params, {}, batch, jax.random.key(1))
    lf, _ = m_f.loss(params, {}, batch, jax.random.key(1))
    np.testing.assert_allclose(float(lx), float(lf), rtol=1e-4)


# ---------------------------------------------------------------------------
# lever surface (round 6): kernel-path shapes — the Pallas kernels
# ACTUALLY run here (interpret mode), no fallback
# ---------------------------------------------------------------------------

KS, KD = 256, 64          # S % 128 == 0, D == 64: Mosaic-tileable


def _qkv_kernel(seed=0):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(B, KS, H, KD).astype(np.float32)
                             * 0.4) for _ in range(3))


@pytest.mark.parametrize("kw", [
    dict(block_q=64, block_k=128),
    dict(block_q=32, block_k=128, bwd_block=256),
    dict(block_q=256, block_k=256),
    dict(block_q=128, block_k=128, bwd_variant="fused"),
])
def test_kernel_path_nondefault_blocks_match_xla(kw):
    q, k, v = _qkv_kernel(10)
    assert kernel_engages(KS, KD, **{a: b for a, b in kw.items()
                                     if a != "bwd_variant"})
    for causal in (False, True):
        want = multi_head_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal=causal, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kw", [dict(bwd_block=256),
                                dict(bwd_variant="fused")])
def test_bwd_lever_grads_match_xla(causal, kw):
    """The wider-block split bwd and the fused bwd are real gradient
    paths, not just forward levers."""
    q, k, v = _qkv_kernel(11)

    def loss(fn, **fkw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, **fkw) ** 2)

    ref = jax.grad(loss(multi_head_attention, causal=causal),
                   argnums=(0, 1, 2))(q, k, v)
    fl = jax.grad(loss(flash_attention, causal=causal, block_q=64,
                       block_k=128, **kw), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ref, fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_bwd_matches_split_bitwise(causal):
    """The fused backward accumulates each gradient in the same order as
    the split kernels (dq over ascending k blocks, dk/dv over ascending
    q blocks) with identical per-block math, so the variants must agree
    BIT-FOR-BIT — any drift means the fused kernel recomputes s/p/ds
    differently than the oracle."""
    q, k, v = _qkv_kernel(12)

    def grads(**kw):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=128, **kw) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(grads(), grads(bwd_variant="fused")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_bwd_matches_split_bitwise_masked():
    q, k, v = _qkv_kernel(13)
    mask = np.ones((B, KS), np.int32)
    mask[:, 200:] = 0
    m = jnp.asarray(mask)

    def grads(**kw):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, mask=m, block_q=64, block_k=128,
            **kw)[:, :200] ** 2), argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(grads(), grads(bwd_variant="fused")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_attention_train_flops_closed_form():
    """9 block-matmul units split (2 fwd + 7 bwd), 7 fused (2 + 5);
    causal halves; each unit is 2·B·S²·hidden·layers."""
    unit = 2 * 2 * 128 ** 2 * 64 * 3
    split = attention_train_flops(2, 128, 64, 3)
    assert split == 9 * unit
    assert attention_train_flops(2, 128, 64, 3,
                                 bwd_variant="fused") == 7 * unit
    assert attention_train_flops(2, 128, 64, 3, causal=True) == 4.5 * unit
    with pytest.raises(ValueError, match="bwd_variant"):
        attention_train_flops(2, 128, 64, 3, bwd_variant="bogus")


def test_effective_bwd_variant_degrades_past_vmem_slab():
    """fused needs an [S, D] f32 dq slab in VMEM: past the limit it
    executes as split — and the MFU accounting must count the split
    matmul count, not the requested variant's."""
    from distributed_tensorflow_example_tpu.ops.pallas.flash_attention \
        import effective_bwd_variant

    assert effective_bwd_variant(4096, 64, "fused") == "fused"
    assert effective_bwd_variant(65536, 64, "fused") == "split"
    assert effective_bwd_variant(65536, 64, "split") == "split"


def test_kernel_engages_matches_fallback_boundary():
    assert kernel_engages(256, 64)
    assert not kernel_engages(256, 32)          # head dim not MXU-aligned
    assert not kernel_engages(250, 64)          # S not divisible
    assert not kernel_engages(256, 64, block_k=96)
    # a bwd_block the sequence can't tile disables the kernel path too
    # (at S=256 it would be CLAMPED to 256 and engage; at S=512 the
    # clamp is a no-op and 512 % 384 != 0 kills the path)
    assert kernel_engages(256, 64, bwd_block=384)
    assert not kernel_engages(512, 64, bwd_block=384)


def test_invalid_lever_values_raise():
    q, k, v = _qkv_kernel(14)
    with pytest.raises(ValueError, match="positive"):
        flash_attention(q, k, v, block_q=0)
    with pytest.raises(ValueError, match="bwd_block"):
        flash_attention(q, k, v, bwd_block=-128)
    with pytest.raises(ValueError, match="bwd_variant"):
        flash_attention(q, k, v, bwd_variant="bogus")


# ---------------------------------------------------------------------------
# config -> call-site plumbing + loud config validation
# ---------------------------------------------------------------------------

def test_config_blocks_reach_kernel(monkeypatch):
    """TrainConfig lever knobs must arrive at the kernel call unchanged
    — the whole point of the plumbing is that a sweep is reproducible
    from flags, so a dropped kwarg is a silent sweep-invalidator."""
    import importlib

    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model

    # the package __init__ re-exports the function under the module's
    # name, so import the MODULE explicitly to patch its attribute
    fa_mod = importlib.import_module(
        "distributed_tensorflow_example_tpu.ops.pallas.flash_attention")

    seen: dict = {}

    def spy(q, k, v, *, mask=None, causal=False, **kw):
        seen.update(kw, causal=causal)
        return jnp.zeros_like(q)

    monkeypatch.setattr(fa_mod, "flash_attention", spy)
    cfg = TrainConfig(model="gpt_tiny", attention_impl="flash",
                      attention_block_q=256, attention_block_k=256,
                      attention_bwd_block=512, attention_bwd="fused")
    m = get_model("gpt_tiny", cfg)
    m.loss(m.init(jax.random.key(0)), {}, m.dummy_batch(2),
           jax.random.key(1))
    assert seen == dict(block_q=256, block_k=256, bwd_block=512,
                        bwd_variant="fused", causal=True)


def test_config_validation_fails_loudly():
    from distributed_tensorflow_example_tpu.config import (
        TrainConfig, flash_attention_kwargs)

    assert flash_attention_kwargs(TrainConfig()) == {}
    with pytest.raises(ValueError, match="attention_impl='flash'"):
        flash_attention_kwargs(TrainConfig(attention_block_q=256))
    with pytest.raises(ValueError, match="multiple of 8"):
        flash_attention_kwargs(TrainConfig(attention_impl="flash",
                                           attention_block_q=12))
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention_kwargs(TrainConfig(attention_impl="flash",
                                           attention_block_k=64))
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention_kwargs(TrainConfig(attention_impl="flash",
                                           attention_bwd_block=100))
    with pytest.raises(ValueError, match="attention_bwd"):
        flash_attention_kwargs(TrainConfig(attention_impl="flash",
                                           attention_bwd="bogus"))


def test_cli_flags_map_to_config():
    from distributed_tensorflow_example_tpu.cli.train import (
        build_parser, config_from_args)

    args = build_parser().parse_args(
        ["--model", "gpt", "--attention", "flash",
         "--attention_block_q", "256", "--attention_block_k", "512",
         "--attention_bwd_block", "512", "--attention_bwd", "fused"])
    cfg = config_from_args(args)
    assert (cfg.attention_block_q, cfg.attention_block_k,
            cfg.attention_bwd_block, cfg.attention_bwd) == \
        (256, 512, 512, "fused")


def test_flash_kwargs_rejected_by_xla_impl():
    q, k, v = _qkv_kernel(15)
    with pytest.raises(ValueError, match="impl='flash'"):
        multi_head_attention(q, k, v, impl="xla",
                             flash_kwargs={"block_q": 256})


# ---------------------------------------------------------------------------
# under a mesh jit partitions: the kernel shard_maps itself (PR 21)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("masked", [False, True])
def test_kernel_under_an_ambient_mesh_matches_unsharded(cpu8, masked):
    """Batch over data=2, heads over model=2: the per-shard kernel is
    the whole computation, so values and gradients equal the unsharded
    call's (the TPU-side reason — Mosaic kernels cannot be
    auto-partitioned — is compiled in tests/test_tpu_compile.py)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_example_tpu.config import MeshShape
    from distributed_tensorflow_example_tpu.parallel.mesh import (
        AxisNames, build_mesh)

    mesh = build_mesh(MeshShape(data=2, model=2), devices=cpu8[:4])
    rs = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rs.randn(4, 128, 2, 64).astype(np.float32) * 0.4)
               for _ in range(3))
    mask = np.ones((4, 128), np.int32)
    mask[1, 100:] = 0
    mask = jnp.asarray(mask) if masked else None

    def loss(q, k, v):
        out = flash_attention(q, k, v, mask=mask, causal=True)
        return jnp.sum(out * out)

    def on_mesh(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    sh = NamedSharding(mesh, P(AxisNames.BATCH, None, AxisNames.MODEL))
    got = jax.jit(on_mesh)(*(jax.device_put(x, sh) for x in (q, k, v)))
    # the manual region shard_map lowers to is there (not a silent
    # bare call that interpret mode would let the partitioner take)
    assert "manual_computation" in jax.jit(on_mesh).lower(q, k, v).as_text()
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# PR 25: tiles chosen from the shape, MXU operands in the input's dtype
# ---------------------------------------------------------------------------

BF16_EPS = float(jnp.finfo(jnp.bfloat16).eps)          # 2**-7


@pytest.mark.parametrize("seq,masked", [(1024, False), (512, True)])
def test_bf16_parity_at_the_schedules_own_tiles(seq, masked):
    """bfloat16 in, no lever set: the kernels run at ``flash_schedule``'s
    tiles with bf16 MXU operands (q, k, v, do, and p / ds cast for their
    matmuls) and f32 statistics and accumulators — the precision of the
    XLA path on the same inputs. Shapes: the gpt2s-train cell's (S=1024,
    causal) and the serving prefill's (S=512, causal + key mask).

    Tolerance, from bf16's epsilon: both results are ROUNDED to bf16 (half
    an ulp each) and round p at different points (XLA the normalised
    probabilities, the kernel the unnormalised ones), so they may differ
    by two ulps of the largest magnitude: 2 * eps * max|want|."""
    from distributed_tensorflow_example_tpu.ops.pallas.flash_attention \
        import flash_schedule

    sch = flash_schedule(seq, KD, jnp.bfloat16)
    assert sch.tileable(seq, KD)
    assert min(sch[:4]) > 128                # retiled, not the old constant
    rs = np.random.RandomState(seq)
    q, k, v = (jnp.asarray(rs.randn(1, seq, H, KD) * 0.5, jnp.bfloat16)
               for _ in range(3))
    w = jnp.asarray(rs.randn(1, seq, H, KD), jnp.float32)
    valid = seq - seq // 4
    mask = None
    if masked:
        m = np.ones((1, seq), np.int32)
        m[:, valid:] = 0
        mask = jnp.asarray(m)
        w = w.at[:, valid:].set(0.0)         # padded rows: no contract

    def xla(q, k, v):
        m4 = None if mask is None else mask[:, None, None, :]
        return multi_head_attention(q, k, v, mask=m4, causal=True)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask=mask, causal=True)

    def close(got, want):
        got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
        np.testing.assert_allclose(
            got, want, rtol=0, atol=2 * BF16_EPS * np.abs(want).max())

    got, want = flash(q, k, v), xla(q, k, v)
    assert got.dtype == jnp.bfloat16
    close(got[:, :valid], want[:, :valid])

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss(xla), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_xla):
        assert a.dtype == jnp.bfloat16
        close(a, b)


@pytest.mark.parametrize("seq,d,dtype,want", [
    # the gpt2s-train cell: one forward step a batch-head, four backward
    (1024, 64, jnp.bfloat16, (1024, 1024, 512, 512, "fused")),
    # float32 operands: the sweep found the same winner
    (1024, 64, jnp.float32, (1024, 1024, 512, 512, "fused")),
    # the serving prefill (forward only): the whole prompt in one tile
    (512, 64, jnp.bfloat16, (512, 512, 512, 512, "fused")),
    (4096, 64, jnp.bfloat16, (1024, 1024, 512, 512, "fused")),
    # the itemsize counts: the fused backward holds an [S, D] f32 dq
    # slab and a double-buffered [S, D] dq block in the input's dtype,
    # and past their VMEM limit the choice degrades to split
    (16384, 64, jnp.bfloat16, (1024, 1024, 512, 512, "fused")),
    (32768, 64, jnp.bfloat16, (1024, 1024, 512, 512, "split")),
    (8192, 64, jnp.float32, (1024, 1024, 512, 512, "fused")),
    (16384, 64, jnp.float32, (1024, 1024, 512, 512, "split")),
    (65536, 64, jnp.bfloat16, (1024, 1024, 512, 512, "split")),
    # only the largest candidate that divides S: 768 = 3 x 256
    (768, 64, jnp.bfloat16, (256, 256, 256, 256, "fused")),
    (384, 128, jnp.bfloat16, (128, 128, 128, 128, "fused")),
    # S=128 keeps the one tile it always had
    (128, 64, jnp.bfloat16, (128, 128, 128, 128, "fused")),
])
def test_flash_schedule_is_a_rule_on_the_shape(seq, d, dtype, want):
    from distributed_tensorflow_example_tpu.ops.pallas.flash_attention \
        import FlashSchedule, effective_bwd_variant, flash_schedule

    sch = flash_schedule(seq, d, dtype)
    assert sch == FlashSchedule(*want)
    assert kernel_engages(seq, d)
    # what the MFU accounting counts is what the schedule runs, and a
    # forced fused degrades where the chosen one does
    assert effective_bwd_variant(seq, d, None, dtype) == sch.bwd_variant
    assert effective_bwd_variant(seq, d, "fused", dtype) == sch.bwd_variant
    assert effective_bwd_variant(seq, d, "split", dtype) == "split"


def test_flash_schedule_grid_steps_and_log_line():
    from distributed_tensorflow_example_tpu.ops.pallas.flash_attention \
        import describe_attention, flash_schedule

    sch = flash_schedule(1024, 64, jnp.bfloat16)
    # per batch-head: 1 + 4 steps where block 128 made 64 + 2 * 64
    assert sch.grid_steps(1024) == (1, 4)
    line = describe_attention(1024, 64, "bfloat16")
    assert "fwd 1024x1024" in line and "bwd fused 512x512" in line
    assert "fwd 1, bwd 4" in line
    # a set lever shows in the line, as it reaches the kernel
    assert "bwd split 256x256" in describe_attention(
        1024, 64, "bfloat16", bwd_block=256, bwd_variant="split")
    assert "falls back to XLA" in describe_attention(250, 64)


def test_levers_override_the_schedule():
    from distributed_tensorflow_example_tpu.ops.pallas.flash_attention \
        import resolve_schedule

    def resolved(**levers):
        return tuple(resolve_schedule(1024, 64, jnp.bfloat16, **levers))

    assert resolved() == (1024, 1024, 512, 512, "fused")
    # a set forward tile tiles the backward too, as it always has
    assert resolved(block_q=256) == (256, 1024, 256, 1024, "fused")
    assert resolved(block_q=256, block_k=512, bwd_block=128) == \
        (256, 512, 128, 128, "fused")
    assert resolved(bwd_block=256) == (1024, 1024, 256, 256, "fused")
    assert resolved(bwd_variant="split") == (1024, 1024, 512, 512, "split")
    # levers clamp to the sequence, as before
    assert tuple(resolve_schedule(256, 64, block_q=512, block_k=512)) == \
        (256, 256, 256, 256, "fused")


@pytest.mark.parametrize("flag,want", [
    ([], {}),
    (["--attention_bwd", "auto"], {}),
    (["--attention_bwd", "split"], {"bwd_variant": "split"}),
    (["--attention_bwd", "fused"], {"bwd_variant": "fused"}),
])
def test_attention_bwd_flag_forces_or_leaves_the_choice(flag, want):
    """``--attention_bwd`` keeps its meaning: split and fused both FORCE
    their kernel (fused with its VMEM degrade); left at its default
    (auto) the lever is unset and the schedule chooses."""
    from distributed_tensorflow_example_tpu.cli.train import (
        build_parser, config_from_args)
    from distributed_tensorflow_example_tpu.config import (
        flash_attention_kwargs)
    from distributed_tensorflow_example_tpu.ops.pallas.flash_attention \
        import resolve_schedule

    cfg = config_from_args(build_parser().parse_args(
        ["--model", "gpt", "--attention", "flash", *flag]))
    kw = flash_attention_kwargs(cfg)
    assert kw == want
    assert resolve_schedule(1024, 64, **kw).bwd_variant == \
        want.get("bwd_variant", "fused")


@pytest.mark.parametrize("seq,d", [(64, 64), (250, 64), (1000, 64),
                                   (256, 32), (1024, 96)])
def test_unfriendly_shapes_still_fall_back(seq, d):
    """No tile of the schedule turns a shape the kernels never took into
    one they take: short, odd or MXU-unaligned shapes go to XLA."""
    assert not kernel_engages(seq, d)
    rs = np.random.RandomState(seq + d)
    q, k, v = (jnp.asarray(rs.randn(1, seq, 2, d).astype(np.float32) * 0.4)
               for _ in range(3))
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(multi_head_attention(q, k, v, causal=True)),
        rtol=1e-5, atol=1e-6)


def test_default_call_runs_the_chosen_schedule(monkeypatch):
    """No lever set: the tiles that reach the kernels are
    ``flash_schedule``'s for the call's own (S, D, dtype)."""
    import importlib
    fa_mod = importlib.import_module(
        "distributed_tensorflow_example_tpu.ops.pallas.flash_attention")
    seen = []
    real = fa_mod._make_flash

    def spy(*args):
        seen.append(args)
        return real(*args)

    monkeypatch.setattr(fa_mod, "_make_flash", spy)
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv_kernel(16))
    flash_attention(q, k, v, causal=True)
    flash_attention(q, k, v, causal=True, bwd_variant="split")
    want = tuple(fa_mod.flash_schedule(KS, KD, jnp.bfloat16))
    assert seen[0] == (H, *want, True, False)
    assert seen[1] == (H, *want[:4], "split", True, False)


@pytest.mark.parametrize("seq,levers,want", [
    (128, {}, "attention flash: flash attention falls back to XLA"),
    (64, dict(attention_impl="xla"), "attention xla"),
])
def test_trainer_logs_the_attention_path_once(seq, levers, want):
    """The start-up line beside the parameter count: which attention
    runs and, for flash, the schedule the kernels chose (gpt_tiny's
    head dim is 32, so its flash call reads as the XLA fallback; the
    chosen-tiles text is ``describe_attention``'s, tested above)."""
    import logging

    from distributed_tensorflow_example_tpu.config import (DataConfig,
                                                           MeshShape,
                                                           TrainConfig)
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.train.trainer import Trainer

    cfg = TrainConfig(**{"model": "gpt_tiny", "attention_impl": "flash",
                         "mesh": MeshShape(data=8),
                         "data": DataConfig(batch_size=8, seq_len=seq),
                         **levers})
    model = get_model("gpt_tiny", cfg)
    batch = model.dummy_batch(8)
    trainer = Trainer(model, cfg, {k: v[:, :seq] for k, v in batch.items()})
    # the dtx logger does not propagate to root: a handler of our own
    records = []

    class _Grab(logging.Handler):
        def emit(self, record):
            records.append(record)

    lg, grab = logging.getLogger("dtx.trainer"), _Grab(logging.INFO)
    lg.addHandler(grab)
    try:
        trainer.initialize()
    finally:
        lg.removeHandler(grab)
    lines = [r.getMessage() for r in records
             if "attention" in r.getMessage()]
    assert len(lines) == 1 and lines[0].startswith("model gpt_tiny: ")
    assert want in lines[0]

"""The main path's Pallas kernels, compiled for a described v5e.

Interpret mode (what every other CPU test runs) cannot see what the TPU
compiler refuses — a block whose lane width is not a 128-multiple, a
Mosaic kernel handed to the automatic partitioner. The compiler is
installed here and compiles for a chip that is described and not
attached (on-chip-measurement guide §2 step 3), so the kernels of the
main path are compiled at GPT-2-small widths (12 heads x 64) as tier-1
tests: a refusal shows here at no chip time. Nothing runs; a compile
that passes is not a chip run.
"""

import functools
import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # compiler logs off /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from distributed_tensorflow_example_tpu import serving
from distributed_tensorflow_example_tpu.parallel.mesh import AxisNames

# the modules, not the same-named functions ops.pallas re-exports
decode_mod = importlib.import_module(
    "distributed_tensorflow_example_tpu.ops.pallas.decode_attention")
flash_mod = importlib.import_module(
    "distributed_tensorflow_example_tpu.ops.pallas.flash_attention")

H, D = 12, 64                      # GPT-2-small heads x head_dim
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def chip():
    """Devices of a described v5e 2x2 host; kernels lower for the chip
    (``_interpret`` held false — the code under test asks
    ``jax.default_backend()``, which is the CPU here) with the
    persistent compilation cache off: such a compile can be written to
    it but never read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mp = pytest.MonkeyPatch()
    mp.setattr(decode_mod, "_interpret", lambda: False)
    mp.setattr(flash_mod, "_interpret", lambda: False)
    yield list(topo.devices)
    mp.undo()
    jax.config.update("jax_enable_compilation_cache", cache_was)


def compile_text(fn, *specs) -> str:
    """Compile ``fn`` for the shardings its specs carry; the text of the
    compiled program. Raises what the chip's compiler would raise."""
    return jax.jit(fn).lower(*specs).compile().as_text()


def on(device_or_sharding, shape, dtype=BF16):
    sh = device_or_sharding
    if not isinstance(sh, jax.sharding.Sharding):
        sh = SingleDeviceSharding(sh)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


# ---------------------------------------------------------------------------
# flash attention: the train step's kernels
# ---------------------------------------------------------------------------

def _flash_loss(q, k, v, mask, *, causal, bwd_variant):
    out = flash_mod.flash_attention(
        q, k, v, mask=mask if not causal else None, causal=causal,
        bwd_variant=bwd_variant)
    return jnp.sum(out.astype(jnp.float32))


@pytest.mark.parametrize("name,batch,seq,causal,bwd", [
    ("fwd_causal_s512", 32, 512, True, None),
    ("bwd_split_causal_s512", 32, 512, True, "split"),
    ("bwd_fused_causal_s512", 32, 512, True, "fused"),
    ("fwd_masked_s4096", 4, 4096, False, None),
])
def test_flash_kernels_compile(chip, name, batch, seq, causal, bwd):
    assert flash_mod.kernel_engages(seq, D)
    fn = functools.partial(_flash_loss, causal=causal,
                           bwd_variant=bwd or "split")
    if bwd:
        fn = jax.grad(fn, argnums=(0, 1, 2))
    qkv = on(chip[0], (batch, seq, H, D))
    text = compile_text(fn, qkv, qkv, qkv,
                        on(chip[0], (batch, seq), jnp.int32))
    # fwd alone is one kernel; fwd + split bwd three, fused bwd two
    assert text.count("tpu_custom_call") >= {None: 1, "split": 3,
                                             "fused": 2}[bwd]


@pytest.mark.parametrize("name,batch,seq,masked,grad", [
    ("gpt2s_train_cell", 16, 1024, False, True),
    ("serving_prefill", 1, 512, True, False),
])
def test_flash_chosen_schedule_compiles(chip, name, batch, seq, masked,
                                        grad):
    """No lever set: the tiles ``flash_schedule`` chooses for the
    gpt2s-train cell's shape (B=16, S=1024, bf16, causal; forward and
    the backward variant it picked) and for the serving prefill's (one
    prompt, S=512, key mask + causal, forward only) are tiles Mosaic
    takes, with the VMEM their kernels ask for."""
    sch = flash_mod.flash_schedule(seq, D, BF16)
    assert flash_mod.kernel_engages(seq, D) and min(sch[:4]) > 128

    def fn(q, k, v, mask):
        out = flash_mod.flash_attention(
            q, k, v, mask=mask if masked else None, causal=True)
        return jnp.sum(out.astype(jnp.float32)) if grad else out

    if grad:
        fn = jax.grad(fn, argnums=(0, 1, 2))
    qkv = on(chip[0], (batch, seq, H, D))
    text = compile_text(fn, qkv, qkv, qkv,
                        on(chip[0], (batch, seq), jnp.int32))
    assert "flash_fwd" in text
    if grad:
        # the names train_flash_bwd_ms reads by pattern, whatever variant
        wanted = {"fused": ["flash_bwd_fused"],
                  "split": ["flash_bwd_dq", "flash_bwd_dkv"]}
        for kernel in wanted[sch.bwd_variant]:
            assert kernel in text
    # the kernel's operands at the folded [B*H, S, D] shape, in bf16
    assert f"bf16[{batch * H},{seq},{D}]" in text


@pytest.mark.parametrize("seq,dtype,want", [
    # the longest sequence whose fused backward the schedule still
    # picks, by itemsize: slab + dq block are 16 MiB / 12 MiB of VMEM
    (16384, BF16, "fused"), (8192, jnp.float32, "fused"),
    # past it the default call runs the split pair, as it did when
    # split was the default (fused there: RESOURCE_EXHAUSTED in vmem)
    (32768, BF16, "split"), (16384, jnp.float32, "split"),
])
def test_flash_long_sequence_backward_fits_vmem(chip, seq, dtype, want):
    """No lever set, long S: the fused backward's [S, D] f32 dq slab and
    double-buffered [S, D] dq block are in the VMEM the kernel asks for,
    and where they outgrow ``_FUSED_SLAB_LIMIT`` the schedule runs the
    split pair instead of failing to compile."""
    assert flash_mod.flash_schedule(seq, D, dtype).bwd_variant == want
    fn = jax.grad(functools.partial(_flash_loss, mask=None, causal=True,
                                    bwd_variant=None), argnums=(0, 1, 2))
    qkv = on(chip[0], (1, seq, 2, D), dtype)
    text = compile_text(fn, qkv, qkv, qkv)
    assert ("flash_bwd_fused" in text) == (want == "fused")
    assert ("flash_bwd_dq" in text) == (want == "split")


def test_flash_partitions_itself_over_a_four_chip_mesh(chip):
    """What ``cli.train --mesh data=4 --attention flash`` compiles: the
    batch sharded over four chips under the mesh SyncReplicas makes
    ambient. Bare, the TPU lowering refuses ("Mosaic kernels cannot be
    automatically partitioned"); the kernel must shard_map itself."""
    mesh = Mesh(np.asarray(chip).reshape(4, 1, 1, 1, 1, 1), AxisNames.ALL)
    batch_sh = NamedSharding(mesh, P(AxisNames.BATCH))

    def step(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.grad(functools.partial(
                _flash_loss, mask=None, causal=True,
                bwd_variant="split"), argnums=(0, 1, 2))(q, k, v)

    qkv = on(batch_sh, (32, 512, H, D))
    text = compile_text(step, qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3
    # each chip runs the kernel on its own 8 rows: [8*12, 512, 64]
    assert f"bf16[{8 * H},512,{D}]" in text


# ---------------------------------------------------------------------------
# decode attention: the serving step's kernels
# ---------------------------------------------------------------------------

SLOTS, T, POOL_BLOCKS, BS = 8, 256, 64, 128


def _slab_specs(dev, heads=H, head_dim=D):
    kv = on(dev, (SLOTS, T, heads, head_dim))
    row = on(dev, (SLOTS,), jnp.int32)
    return on(dev, (SLOTS, heads, head_dim)), kv, kv, row, row


def _paged_specs(dev, heads=H, head_dim=D, dtype=BF16, *, rows=SLOTS,
                 width=T // BS, blocks=POOL_BLOCKS):
    pool = on(dev, (blocks, BS, heads * head_dim), dtype)
    row = on(dev, (rows,), jnp.int32)
    specs = (on(dev, (rows, heads, head_dim)), pool, pool,
             on(dev, (rows, width), jnp.int32), row, row)
    if dtype == jnp.int8:
        scale = on(dev, (blocks, BS), jnp.float32)
        specs += (scale, scale)
    return specs


@pytest.mark.parametrize("name", ["slab", "paged", "paged_int8",
                                  "paged_cell", "paged_cell_int8",
                                  "paged_cell_verify", "paged_d128"])
def test_decode_kernels_compile(chip, name):
    """``paged_cell*``: the schedule ``paged_schedule`` chooses at
    GPT-2's served shape (64 rows, or a verify program's 4 x 64, of
    6-entry tables over every layer's blocks, [12 * 385, 128, 768]),
    bfloat16 and the int8 pair; ``paged_d128``: 6 heads x 128."""
    if name == "slab":
        assert decode_mod.tile_friendly(T, H, D)
        text = compile_text(decode_mod._dispatch, *_slab_specs(chip[0]))
    else:
        dtype = jnp.int8 if name.endswith("int8") else BF16
        heads, head_dim = (6, 128) if name == "paged_d128" else (H, D)
        assert decode_mod.paged_tile_friendly(BS, heads, head_dim, dtype)
        shape = {}
        if "cell" in name:
            shape = dict(
                rows=CELL["slots"] * (4 if name.endswith("verify") else 1),
                width=CELL["blocks_per_slot"],
                blocks=CELL["layers"] * CELL["blocks"])
            sch = decode_mod.paged_schedule(
                shape["rows"], H, D, BS, shape["width"], dtype)
            assert sch.entries == CELL["blocks_per_slot"]   # one step a row
        text = compile_text(
            decode_mod._paged_dispatch,
            *_paged_specs(chip[0], heads, head_dim, dtype, **shape))
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# the served GPT-2 programs at the gpt2s-serve cells' shape: the paged cache
# is written and read where it lies
# ---------------------------------------------------------------------------

CELL = dict(layers=12, blocks=385, slots=64, blocks_per_slot=6,
            prompt_len=512)
_ITEMSIZE = {"bf16": 2, "s8": 1, "f32": 4, "s32": 4, "pred": 1}


def _pool_specs(dev, quant):
    """The engine's pool as the served programs take it: [L, N, Bs, H*D]
    K and V (int8 with their [L, N, Bs] scale pools when ``quant``)."""
    shape = (CELL["layers"], CELL["blocks"], BS, H * D)
    pool = {"cache_k": on(dev, shape, jnp.int8 if quant else BF16),
            "cache_v": on(dev, shape, jnp.int8 if quant else BF16)}
    if quant:
        pool.update({k + "_scale": on(dev, shape[:3], jnp.float32)
                     for k in tuple(pool)})
    return pool


@pytest.fixture(scope="module")
def gpt2_small(chip):
    """gpt2-small as the serving cells build it, its parameter tree and
    stacked decode weights as shapes on the described chip."""
    from distributed_tensorflow_example_tpu.models.gpt import GPT, GPTConfig
    dev = chip[0]
    cfg = GPTConfig.small()
    cfg.vocab_size, cfg.dropout = 50257, 0.0
    model = GPT(cfg, dtype=BF16, attention_impl="flash",
                decode_attention_impl="pallas")
    params = jax.eval_shape(model.init, jax.random.key(0))
    stacked = jax.eval_shape(model.stack_decode_params, params)
    place = functools.partial(jax.tree_util.tree_map,
                              lambda x: on(dev, x.shape, x.dtype))
    return model, place(params), place(stacked)


def _served_program(model, name):
    """``serving._export_stepwise_paged``'s decode / verify / prefill
    function over (pool, weights, inputs): the pool first, to be donated
    as ``StepwiseGenerator`` donates it."""
    def unpack(pool):
        return {k[len("cache_"):]: v for k, v in pool.items()}

    def pack(logits, new):
        out = {"logits": logits,
               **{"cache_" + k: v for k, v in new.items()}}
        if name != "verify":                # the host reads every lane
            out["ids"] = serving._greedy_ids(logits)
        return out

    if name in ("prefill", "prefill_chunk"):
        # (params, ids, mask[, start], k_pool, v_pool, table_row[, blocks])
        lead = 2 if name == "prefill" else 3

        def fn(pool, params, *inputs):
            p = unpack(pool)
            out = getattr(model, "paged_" + name)(
                params, *inputs[:lead], p.pop("k"), p.pop("v"),
                *inputs[lead:], **p)
            return pack(out[0], dict(zip(
                ("k", "v", "k_scale", "v_scale"), out[1:])))
    elif name == "verify":
        def fn(pool, params, stacked, bt, tok, pos, pad, alive, n_tok):
            return pack(*model.decode_verify_batched_paged(
                params, stacked, unpack(pool), bt, tok, pos, pad, alive,
                n_tok))
    else:
        def fn(pool, params, stacked, bt, tok, pos, pad, alive):
            return pack(*model.decode_step_batched_paged(
                params, stacked, unpack(pool), bt, tok, pos, pad, alive))
    return fn


@pytest.fixture(scope="module")
def served(chip, gpt2_small):
    """``served(name, quant)``: that program of gpt2-small compiled for
    the described chip at the serving cells' shape (pool donated), once
    for the tests of this file that read it: ``(compiled, pool specs)``."""
    dev = chip[0]
    model, params, stacked = gpt2_small
    row = on(dev, (CELL["slots"],), jnp.int32)
    table_row = on(dev, (CELL["prompt_len"] // BS,), jnp.int32)

    @functools.cache
    def compile_served(name, quant):
        pool = _pool_specs(dev, quant)
        if name == "prefill":
            ids = on(dev, (1, CELL["prompt_len"]), jnp.int32)
            args = (params, ids, ids, table_row)
        elif name == "prefill_chunk":
            ids = on(dev, (1, BS), jnp.int32)
            args = (params, ids, ids, on(dev, (), jnp.int32), table_row,
                    on(dev, (1,), jnp.int32))
        else:
            tok = (on(dev, (CELL["slots"], 4), jnp.int32)
                   if name == "verify" else row)
            args = (params, stacked,
                    on(dev, (CELL["slots"], CELL["blocks_per_slot"]),
                       jnp.int32),
                    tok, row, row, row) + (
                        (row,) if name == "verify" else ())
        return (jax.jit(_served_program(model, name), donate_argnums=0)
                .lower(pool, *args).compile(), pool)
    return compile_served


def _copies_of(text, nbytes):
    """The ``copy`` operations of a compiled program's text that move at
    least ``nbytes``."""
    return [m.group(0) for m in re.finditer(
                r"(\w+)\[([\d,]+)\]\S* copy\(", text)
            if _ITEMSIZE.get(m.group(1), 4) * np.prod(
                [int(x) for x in m.group(2).split(",")]) >= nbytes]


@pytest.mark.parametrize("name,quant", [
    ("decode", False), ("decode", True), ("verify", False),
    ("prefill", False), ("prefill", True), ("prefill_chunk", False)])
def test_served_programs_leave_the_pool_where_it_lies(served, name, quant):
    """``jit_decode`` (float and int8 pools), the verify expansion at
    K = 4, ``jit_prefill`` and one 128-token chunk of a chunked prefill,
    of gpt2-small at the serving cells' shape (12 layers, 385 blocks of
    128 tokens, 64 slots of 6 blocks, 512-token prompts), pools donated:
    (a) no ``copy`` as large as one layer's slice of a pool (with
    [.., H, D] pools scanned as xs/ys there were ten: eight a layer in
    the scan and two whole pools after it), (b) every ``cache_*`` input
    is aliased to an output, (c) under 256 MB of temporaries (were
    3.10 GB)."""
    compiled, pool = served(name, quant)
    text = compiled.as_text()
    if name != "prefill_chunk":             # (its attention is XLA's)
        assert "tpu_custom_call" in text    # the Pallas kernels
    layer_slice = CELL["blocks"] * BS * H * D * (1 if quant else 2)
    big = _copies_of(text, layer_slice)
    assert not big, big
    if name in ("decode", "verify"):
        # the layer scan and nothing else: the benchmark's readers pair a
        # decode step's span with the ONE `while` of its program
        assert len(re.findall(r" while\(", text)) == 1
    # the donated pool's leaves are parameters 0..n-1; each one must be
    # the buffer of an output: "{out}: (param, {}, may-alias)"
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
        re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1))}
    assert aliased == set(range(len(pool))), aliased
    assert compiled.memory_analysis().temp_size_in_bytes < 256e6


def test_decode_program_hands_ids_without_a_copy(served):
    """``jit_decode`` at the serving cells' shape returns each slot's
    greedy id, ``s32[64]``, beside the ``f32[64, 50257]`` logits (PR 36:
    the host fetches 256 bytes a step, and the 12.9 MB only while a row
    samples), and the argmax that makes it costs no ``copy`` operation of
    the logits (none of 12.9 MB or more) nor of a pool (the test above).
    What the compiler does do: the head writes the logits to fast memory
    (``S(1)``), where the reduce reads them, and one asynchronous
    ``copy-start`` / ``copy-done`` puts them in the output buffer beside
    it: 0.011 ms a step on the chip (``benchmark/records/pr36``)."""
    text = served("decode", False)[0].as_text()
    entry = text[text.index("ENTRY"):]
    result = re.search(r"ROOT [^\n]* = \(([^\n]*?)\) tuple\(", entry).group(1)
    slots, vocab = CELL["slots"], 50257
    assert re.search(rf"s32\[{slots}\]", result), result
    assert re.search(rf"f32\[{slots},{vocab}\]", result), result
    copied = _copies_of(text, 4 * slots * vocab)
    assert not copied, copied
    moved = [ln for ln in text.splitlines()
             if f"f32[{slots},{vocab}]" in ln and "copy-start(" in ln]
    assert len(moved) <= 1, moved


# ---------------------------------------------------------------------------
# the predicates say what the compiler says
# ---------------------------------------------------------------------------

def _compiles(fn, *specs) -> bool:
    try:
        compile_text(fn, *specs)
    except Exception:  # noqa: BLE001 — any refusal of the TPU lowering
        return False
    return True


def _raw_flash_fwd(chip, block_k):
    q3 = on(chip[0], (4 * H, 512, D))
    return _compiles(
        functools.partial(flash_mod._fwd, heads=H, blk_q=128,
                          blk_k=block_k, causal=False),
        q3, q3, q3, on(chip[0], (4, 512), jnp.int32))


@pytest.mark.parametrize("kernel,shape,accepted", [
    ("slab", dict(heads=12, head_dim=64), True),
    ("slab", dict(heads=12, head_dim=96), False),
    ("paged", dict(heads=12, head_dim=64), True),
    ("paged", dict(heads=12, head_dim=96), True),
    ("paged", dict(heads=96, head_dim=128), False),
    ("flash", dict(block_k=128), True),
    ("flash", dict(block_k=64), False),
])
def test_predicates_agree_with_the_compiler(chip, kernel, shape, accepted):
    """Each predicate against the RAW kernel (its dispatcher bypassed):
    what it admits compiles, what it refuses the compiler refuses too —
    and a forced ``impl="pallas"`` on a refused shape raises the
    predicate's ValueError before any lowering is tried."""
    if kernel == "flash":
        assert flash_mod.kernel_engages(512, D, **shape) is accepted
        assert _raw_flash_fwd(chip, **shape) is accepted
        return
    h, d = shape["heads"], shape["head_dim"]
    if kernel == "slab":
        assert decode_mod.tile_friendly(T, h, d) is accepted
        assert _compiles(decode_mod._dispatch,
                         *_slab_specs(chip[0], h, d)) is accepted
    else:
        # the paged kernel holds whole [BS, H*D] blocks, every head at
        # once: any head size whose blocks are whole tiles, until a block
        # passes VMEM (the raw kernel at its least schedule, one table
        # entry a grid step, is what the compiler is asked)
        assert decode_mod.paged_tile_friendly(BS, h, d) is accepted
        assert _compiles(
            functools.partial(decode_mod._paged_dispatch,
                              schedule=decode_mod.PagedSchedule(1, 0)),
            *_paged_specs(chip[0], h, d)) is accepted
    if not accepted:
        q = jnp.zeros((1, h, d))
        with pytest.raises(ValueError, match="head dim"):
            if kernel == "slab":
                decode_mod.decode_attention(
                    q, jnp.zeros((1, 128, h, d)), jnp.zeros((1, 128, h, d)),
                    pos=jnp.int32(0), pad=jnp.zeros((1,), jnp.int32),
                    impl="pallas")
            else:
                decode_mod.paged_decode_attention(
                    q, jnp.zeros((2, BS, h * d)), jnp.zeros((2, BS, h * d)),
                    block_tables=jnp.zeros((1, 1), jnp.int32),
                    pos=jnp.zeros((1,), jnp.int32),
                    pad=jnp.zeros((1,), jnp.int32), impl="pallas")


# ---------------------------------------------------------------------------
# the block-diffusion decoder's kernels at SDAR-30B-A3B's widths:
# 32 query heads over 4 KV heads x 128, blocks of 4 lanes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["block_step_attention", "prefill_flash",
                                  "grouped_matmul",
                                  "grouped_matmul_prefill"])
def test_block_decoder_kernels_compile(chip, name):
    """``paged_block_attn`` over a [layers x blocks, 128, 4 x 128] pool
    view with 32 rows a KV head; ``flash_fwd`` under ``causal_block`` 4
    at S=4096; the expert layer's ``ragged_dot`` (a Mosaic grouped
    matmul on the TPU) over 128 experts at the block step's 2,048 (row,
    expert) pairs and the prefill's 32,768, at the tiles
    ``ops/moe.ragged_tiling`` hands the compiler."""
    dev = chip[0]
    if name == "block_step_attention":
        slots, kvh, rows, d, nb = 64, 4, 32, 128, 34
        assert decode_mod.block_tile_friendly(128, rows, d)
        pool = on(dev, (6 * 2177, 128, kvh * d))
        text = compile_text(
            decode_mod._block_dispatch, on(dev, (slots, kvh, rows, d)),
            pool, pool, on(dev, (slots, nb), jnp.int32),
            on(dev, (slots,), jnp.int32))
        assert "paged_block_attn" in text
    elif name == "prefill_flash":
        q = on(dev, (1, 4096, 32, 128))
        text = compile_text(
            functools.partial(flash_mod.flash_attention, causal=True,
                              causal_block=4), q, q, q)
        assert "flash_fwd" in text
    else:
        from distributed_tensorflow_example_tpu.ops.moe import moe_dropless
        experts = {"gate": on(dev, (128, 2048, 768)),
                   "up": on(dev, (128, 2048, 768)),
                   "down": on(dev, (128, 768, 2048))}
        rows = 256 if name == "grouped_matmul" else 4096
        text = compile_text(
            functools.partial(moe_dropless, top_k=8),
            on(dev, (rows, 2048), jnp.float32), on(dev, (2048, 128)),
            experts)
        assert "ragged-dot" in text
        for tile in ("128,2048,768", "128,768,2048"):
            assert f'ragged_dot_tiling="{tile}"' in text
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# a kind a layer: the one-token decode step and the prefill chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["decode", "prefill_chunk"])
def test_state_programs_update_their_state_where_it_lies(chip, name):
    """``kimi_linear``'s two served programs at the ``kimi-serve-backlog``
    cell's shape (5 layers, 128 held experts, half the vocabulary; 128
    slots, 17,409 latent blocks of 128 tokens, 1,024-token chunks), the
    state donated: (a) no ``copy`` as large as one KDA layer's recurrent
    rows (268 MB) or a tenth of the latent pool (with rows of 576 values
    the chip lays the pool out tokens-minor and the decode step copied
    it four times, 2.57 GB each), (b) every ``cache_*`` input is aliased
    to an output, (c) under 512 MB of temporaries, (d) the latent
    attention is the Pallas kernel in the decode step."""
    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    mla_mod = importlib.import_module(
        "distributed_tensorflow_example_tpu.ops.mla")
    mp = pytest.MonkeyPatch()
    mp.setattr(mla_mod, "_interpret", lambda: False)
    try:
        dev = chip[0]
        model = get_model("kimi_linear", TrainConfig(
            model="kimi_linear", dtype="bfloat16", param_dtype="bfloat16",
            num_layers=5))
        model.cfg.experts_held, model.cfg.vocab_held = 128, 81920
        slots, bs, chunk, prompt, new = 128, 128, 1024, 16384, 1024
        nb = (prompt + new) // bs
        params = jax.tree_util.tree_map(
            lambda x: on(dev, x.shape, x.dtype),
            jax.eval_shape(model.init, jax.random.key(0)))
        specs = model.state_specs(slots=slots, num_blocks=1 + slots * nb,
                                  block_size=bs)
        state = {k: on(dev, tuple(v["shape"]), jnp.dtype(v["dtype"]))
                 for k, v in specs.items()}
        i32 = functools.partial(on, dev, dtype=jnp.int32)
        if name == "decode":
            fn = lambda st, p, bt, tok, pos, alive: model.decode_step(  # noqa: E731
                p, st, bt, tok, pos, alive, attention="pallas")
            args = (i32((slots, nb)), i32((slots,)), i32((slots,)),
                    i32((slots,)))
        else:
            fn = lambda st, p, ids, n, start, slot, row, cb: (  # noqa: E731
                model.prefill_chunk(p, st, ids, n, start, slot, row, cb))
            args = (i32((1, chunk)), i32(()), i32(()), i32(()),
                    i32((prompt // bs,)), i32((chunk // bs,)))
        compiled = jax.jit(fn, donate_argnums=0).lower(
            state, params, *args).compile()
    finally:
        mp.undo()
    text = compiled.as_text()
    if name == "decode":
        assert "paged_latent_attn" in text
    layer_rows = int(np.prod(specs["cache_state"]["shape"][1:])) * 4
    pool = int(np.prod(specs["cache_latent"]["shape"])) * 2
    big = [m.group(0) for m in re.finditer(
               r"(\w+)\[([\d,]+)\]\S* copy\(", text)
           if _ITEMSIZE.get(m.group(1), 4) * np.prod(
               [int(x) for x in m.group(2).split(",")])
           >= min(layer_rows, pool // 10)]
    assert not big, big
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
        re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1))}
    assert aliased == set(range(len(state))), aliased
    assert compiled.memory_analysis().temp_size_in_bytes < 512e6


# ---------------------------------------------------------------------------
# a learned sparse selection: the chunk program and the one-token step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["decode", "prefill_chunk"])
def test_selecting_programs_keep_their_three_caches_where_they_lie(chip,
                                                                   name):
    """``dots3_note``'s two served programs at the ``dots3-serve-longctx``
    cell's shape (5 layers, 32 held experts, an eighth of the vocabulary;
    24 slots, 6,145 blocks of 128 tokens, 1,024-token chunks over up to
    32,768 rows), the state donated: (a) the latent pool, the index-key
    pool and the window rings are aliased to outputs and no ``copy`` is a
    tenth of the latent pool; (b) no ``[rows, 64 heads, context]`` score
    tensor and no sort of the chunk's ``[1024, 32768]`` score matrix (the
    chunk finds its k-th largest by bisection; the step sorts one row a
    slot); (c) temporaries under 1 GB beside 8.17 GB of weights; (d) the
    chunk's selected attention is the Pallas kernel, and no ``[128 heads,
    1024, 512]`` score tile is written to memory."""
    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    mla_mod = importlib.import_module(
        "distributed_tensorflow_example_tpu.ops.mla")
    dev = chip[0]
    model = get_model("dots3_note", TrainConfig(
        model="dots3_note", dtype="bfloat16", param_dtype="bfloat16",
        num_layers=5))
    model.cfg.experts_held, model.cfg.vocab_held = 32, 19008
    slots, bs, chunk, prompt, new = 24, 128, 1024, 32256, 512
    nb = (prompt + new) // bs
    params = jax.tree_util.tree_map(
        lambda x: on(dev, x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.key(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 1e9 - 8.17) < 0.05
    specs = model.state_specs(slots=slots, num_blocks=1 + slots * nb,
                              block_size=bs)
    state = {k: on(dev, tuple(v["shape"]), jnp.dtype(v["dtype"]))
             for k, v in specs.items()}
    i32 = functools.partial(on, dev, dtype=jnp.int32)
    if name == "decode":
        fn = lambda st, p, bt, tok, pos, alive: model.decode_step(  # noqa: E731
            p, st, bt, tok, pos, alive)
        args = (i32((slots, nb)), i32((slots,)), i32((slots,)),
                i32((slots,)))
    else:
        fn = lambda st, p, ids, n, start, slot, row, cb: (  # noqa: E731
            model.prefill_chunk(p, st, ids, n, start, slot, row, cb,
                                attention="pallas"))
        args = (i32((1, chunk)), i32(()), i32(()), i32(()),
                i32((-(-prompt // chunk) * chunk // bs,)),
                i32((chunk // bs,)))
    mp = pytest.MonkeyPatch()
    mp.setattr(mla_mod, "_interpret", lambda: False)
    try:
        compiled = jax.jit(fn, donate_argnums=0).lower(
            state, params, *args).compile()
    finally:
        mp.undo()
    text = compiled.as_text()
    if name == "prefill_chunk":
        assert "dsa_selected_attn" in text and "tpu_custom_call" in text
        assert not re.search(r"f32\[128,1024,512\]", text)
    pool = int(np.prod(specs["cache_latent"]["shape"])) * 2
    big = [m.group(0) for m in re.finditer(
               r"(\w+)\[([\d,]+)\]\S* copy\(", text)
           if _ITEMSIZE.get(m.group(1), 4) * np.prod(
               [int(x) for x in m.group(2).split(",")]) >= pool // 10]
    assert not big, big
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
        re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1))}
    assert aliased == set(range(len(state))), aliased
    width = prompt + new
    # what is written to memory: the instructions outside the fusions'
    # own computations (inside one, a value never leaves the core)
    kept, fused = [], False
    for ln in text.splitlines():
        fused = (fused or ln.startswith("%fused_computation")) and ln != "}"
        if not fused:
            kept.append(ln)
    assert not re.search(rf"\[(?:1024|{slots}),64,{width}\]",
                         "\n".join(kept))
    sorts = [re.search(r"\[([\d,]+)\]", ln.split(" = ", 1)[1]).group(1)
             for ln in text.splitlines() if " sort(" in ln and " = " in ln]
    assert all(s != f"1024,{width}" for s in sorts), sorts
    if name == "decode":
        assert f"{slots},{width}" in sorts      # one row a slot
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def _reached(text: str) -> dict:
    """Per computation of a compiled program's text, the lines of every
    computation it reaches (its own, its fusions', its branches')."""
    own, calls, name = {}, {}, None
    for ln in text.splitlines():
        m = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{$", ln)
        if m:
            name = m.group(1)
            own[name], calls[name] = [], set()
        elif name and ln == "}":
            name = None
        elif name:
            own[name].append(ln)
            calls[name].update(re.findall(
                r"(?:calls|to_apply|body|condition|true_computation|"
                r"false_computation)=(%[\w.\-]+)", ln))
            for group in re.findall(r"branch_computations=\{([^}]*)\}", ln):
                calls[name].update(group.split(", "))

    def reach(n, seen):
        if n not in seen and n in own:
            seen.add(n)
            for c in calls[n]:
                reach(c, seen)
        return seen

    return {n: [ln for c in reach(n, set()) for ln in own[c]] for n in own}


def test_dots3_chunk_expert_layer_runs_over_the_bound(chip):
    """dots3-note-prev's chunk expert layer at the cell's shapes (1,024
    rows x top-8 of 256 sigmoid-routed experts, 32 held, [5120, 1536] and
    [1536, 5120] bfloat16): every grouped matmul carries the tile
    ``ops/moe.ragged_tiling`` gives past 4,096 (N cut: the whole matrix
    is 36 MB); the program is a conditional one of whose branches runs
    its grouped matmuls, gathers and selects over the 2,048 rows of
    ``pair_bound`` and keeps ONE ``[8192, ..]`` tensor, the combine's
    gather (every pair its row or the zero row), while the other (the
    whole-width fallback) is the layer over all 8,192; its temporaries
    are the parent's program's (XLA's own tile, every ``T x k`` row) to
    1 %: the fallback reserves them."""
    from distributed_tensorflow_example_tpu.ops import moe as moe_mod
    dev = chip[0]
    t, h, f, e, held = 1024, 5120, 1536, 256, 32
    experts = {"gate": on(dev, (held, h, f)), "up": on(dev, (held, h, f)),
               "down": on(dev, (held, f, h))}

    def compiled():
        def layer(x, router, experts, bias):    # a trace of its own a call
            return moe_mod.moe_dropless(
                x, router, experts, top_k=8, scores="sigmoid",
                select_bias=bias, scale=2.5)
        return jax.jit(layer).lower(
            on(dev, (t, h), jnp.float32), on(dev, (h, e)), experts,
            on(dev, (e,), jnp.float32)).compile()

    got = compiled()
    text = got.as_text()
    gate, down = "128,5120,512", "128,1536,1280"
    tiles = re.findall(r'ragged_dot_tiling="([^"]*)"', text)
    assert sorted(tiles) == sorted([gate, gate, down] * 2), tiles
    reached = _reached(text)
    cond = [ln for ln in text.splitlines() if " conditional(" in ln]
    assert len(cond) == 1, cond
    branches = re.findall(r"(?:true_computation|false_computation)="
                          r"(%[\w.\-]+)", cond[0]) or re.search(
        r"branch_computations=\{([^}]*)\}", cond[0]).group(1).split(", ")
    assert len(branches) == 2

    def wide(branch):
        """The [8192, ..] tensors a branch defines, by element type and
        width (the mask ``pred[8192,1]`` left out)."""
        return sorted({m.group(1) + m.group(2) for ln in reached[branch]
                       for m in [re.search(
                           r" = (\w+)\[8192,(\d{2,})\]", ln)] if m})

    bounded, whole = sorted(branches, key=lambda b: len(wide(b)))
    assert wide(bounded) == ["f325120"], wide(bounded)
    assert {"bf165120", "f321536", "bf161536", "f325120"} <= set(wide(whole))
    for shape in ("bf16[2048,5120]", "f32[2048,1536]", "bf16[2048,1536]",
                  "f32[2048,5120]"):
        assert any(shape in ln for ln in reached[bounded]), shape
        assert not any(shape in ln for ln in reached[whole]), shape
    mp = pytest.MonkeyPatch()
    mp.setattr(moe_mod, "ragged_tiling", lambda *a: None)
    mp.setattr(moe_mod, "pair_bound", lambda pairs, *_: pairs)
    try:
        parent = compiled()
    finally:
        mp.undo()
    # the tile XLA chooses for itself is in the compiled text: 512 rows
    assert set(re.findall(r'ragged_dot_tiling="([^"]*)"',
                          parent.as_text())) == {"512,512,512"}
    # the fallback branch reserves what the parent's layer did (336.3
    # MB) and the conditional 0.6 MB more: no saving, no cost
    assert (got.memory_analysis().temp_size_in_bytes
            < 1.01 * parent.memory_analysis().temp_size_in_bytes)


# ---------------------------------------------------------------------------
# grouped-query attention over a K/V pool and rings: chunk program and step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["decode", "prefill_chunk"])
def test_grouped_query_programs_keep_pool_and_rings_where_they_lie(chip,
                                                                   name):
    """``laguna``'s two served programs at the ``laguna-serve-mixed``
    cell's shape (9 layers, 32 held experts, an eighth of the vocabulary;
    24 slots, 3,073 blocks of 128 tokens, 1,024-token chunks over up to
    16,384 rows), the state donated: (a) the K and V pools and the K and
    V rings are aliased to outputs; no ``copy`` of a step is as large as
    one layer's rings (the first form of the ring attention, a product by
    KV head, cost twelve a step: 25 MB each), none of a chunk a twentieth
    of a pool;
    (b) the one-token attention of the full layers is the Pallas kernel
    ``paged_gqa_attn`` (3 calls), the chunk's two attentions
    ``gqa_chunk_attn`` (3 causal + 6 band), and no ``[heads, 1024,
    1024]`` score tile is written to memory; (c) temporaries under 1 GB
    beside 6.40 GB of weights."""
    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    gqa_mod = importlib.import_module(
        "distributed_tensorflow_example_tpu.ops.gqa")
    dev = chip[0]
    model = get_model("laguna", TrainConfig(
        model="laguna", dtype="bfloat16", param_dtype="bfloat16",
        num_layers=9))
    model.cfg.experts_held, model.cfg.vocab_held = 32, 12544
    slots, bs, chunk, prompt, new = 24, 128, 1024, 15360, 1024
    nb = (prompt + new) // bs
    params = jax.tree_util.tree_map(
        lambda x: on(dev, x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.key(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 1e9 - 6.40) < 0.05
    specs = model.state_specs(slots=slots, num_blocks=1 + slots * nb,
                              block_size=bs)
    state = {k: on(dev, tuple(v["shape"]), jnp.dtype(v["dtype"]))
             for k, v in specs.items()}
    i32 = functools.partial(on, dev, dtype=jnp.int32)
    if name == "decode":
        fn = lambda st, p, bt, tok, pos, alive: model.decode_step(  # noqa: E731
            p, st, bt, tok, pos, alive, attention="pallas")
        args = (i32((slots, nb)), i32((slots,)), i32((slots,)),
                i32((slots,)))
    else:
        fn = lambda st, p, ids, n, start, slot, row, cb: (  # noqa: E731
            model.prefill_chunk(p, st, ids, n, start, slot, row, cb,
                                attention="pallas"))
        args = (i32((1, chunk)), i32(()), i32(()), i32(()),
                i32((-(-prompt // chunk) * chunk // bs,)),
                i32((chunk // bs,)))
    mp = pytest.MonkeyPatch()
    mp.setattr(gqa_mod, "_interpret", lambda: False)
    try:
        compiled = jax.jit(fn, donate_argnums=0).lower(
            state, params, *args).compile()
    finally:
        mp.undo()
    text = compiled.as_text()
    kernel = "paged_gqa_attn" if name == "decode" else "gqa_chunk_attn"
    calls = re.findall(rf"(?m)^\s*%{kernel}[.\d]* = \S+ custom-call\(", text)
    assert len(calls) == (3 if name == "decode" else 9), calls
    if name == "prefill_chunk":
        assert not re.search(r"f32\[(?:48|72),1024,(?:512|1024)\]", text)
    # a step: nothing as large as one layer's rings; a chunk (whose
    # 72-head activations are 38 MB in float32, laid out head-major by
    # XLA on either side of the kernel): nothing a twentieth of a pool
    pool = int(np.prod(specs["cache_k"]["shape"])) * 2
    limit = (int(np.prod(specs["cache_window_k"]["shape"][1:])) * 2
             if name == "decode" else pool // 20)
    big = [m.group(0) for m in re.finditer(
               r"(\w+)\[([\d,]+)\]\S* copy\(", text)
           if _ITEMSIZE.get(m.group(1), 4) * np.prod(
               [int(x) for x in m.group(2).split(",")]) >= limit]
    assert not big, big
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
        re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1))}
    assert aliased == set(range(len(state))), aliased
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


# ---------------------------------------------------------------------------
# dense latent attention over a pool that is the whole state: chunk and step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["decode", "prefill_chunk"])
def test_dense_latent_programs_keep_the_pool_where_it_lies(chip, name):
    """``axk1``'s two served programs at the ``axk1-serve-longctx`` cell's
    shape (5 layers, 12 held experts of 192, an eighth of the vocabulary;
    24 slots, 5,569 blocks of 128 tokens, 1,024-token chunks over up to
    29,696 rows), the state donated: (a) the ONE state array, the 4.56 GB
    latent pool, is aliased to its output, and no ``copy`` is a 200th of
    it in a step or a 100th in a chunk (whose largest are the kernel's own
    operands laid head-major, 25 and 34 MB); (b) the step's absorbed
    attention is the Pallas kernel ``paged_latent_attn`` (5 calls), the
    chunk's expanded attention ``mla_chunk_attn`` (5 calls); no ``[64,
    1024, ctx]`` float32 score tensor and no transposed latent block
    ``[.., 640, 128]`` reach memory; (c) the chunk's expert layers run
    under a ``conditional`` over the bound of their pairs at tiles that
    cut N (``128,7168,256`` / ``128,2048,1024``); (d) temporaries under
    1 GB beside 6.98 GB of weights."""
    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    mla_mod = importlib.import_module(
        "distributed_tensorflow_example_tpu.ops.mla")
    dev = chip[0]
    model = get_model("axk1", TrainConfig(
        model="axk1", dtype="bfloat16", param_dtype="bfloat16",
        num_layers=5))
    model.cfg.experts_held, model.cfg.vocab_held = 12, 20480
    slots, bs, chunk, prompt, new = 24, 128, 1024, 28672, 1024
    nb = (prompt + new) // bs
    params = jax.tree_util.tree_map(
        lambda x: on(dev, x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.key(0)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 1e9 - 6.98) < 0.01
    specs = model.state_specs(slots=slots, num_blocks=1 + slots * nb,
                              block_size=bs)
    assert list(specs) == ["cache_latent"]
    state = {k: on(dev, tuple(v["shape"]), jnp.dtype(v["dtype"]))
             for k, v in specs.items()}
    i32 = functools.partial(on, dev, dtype=jnp.int32)
    if name == "decode":
        fn = lambda st, p, bt, tok, pos, alive: model.decode_step(  # noqa: E731
            p, st, bt, tok, pos, alive, attention="pallas")
        args = (i32((slots, nb)), i32((slots,)), i32((slots,)),
                i32((slots,)))
    else:
        fn = lambda st, p, ids, n, start, slot, row, cb: (  # noqa: E731
            model.prefill_chunk(p, st, ids, n, start, slot, row, cb,
                                attention="pallas"))
        args = (i32((1, chunk)), i32(()), i32(()), i32(()),
                i32((-(-prompt // chunk) * chunk // bs,)),
                i32((chunk // bs,)))
    mp = pytest.MonkeyPatch()
    mp.setattr(mla_mod, "_interpret", lambda: False)
    try:
        compiled = jax.jit(fn, donate_argnums=0).lower(
            state, params, *args).compile()
    finally:
        mp.undo()
    text = compiled.as_text()
    kernel = "paged_latent_attn" if name == "decode" else "mla_chunk_attn"
    calls = re.findall(rf"(?m)^\s*%{kernel}[.\d]* = \S+ custom-call\(", text)
    assert len(calls) == 5, calls
    assert not re.search(r"f32\[64,1024,(?:512|1024|\d{4,})\]", text)
    assert not re.search(r"\[(?:\d+,)*640,128\]", text)
    pool = int(np.prod(specs["cache_latent"]["shape"])) * 2
    assert abs(pool / 1e9 - 4.56) < 0.01
    limit = pool // (200 if name == "decode" else 100)
    big = [m.group(0) for m in re.finditer(
               r"(\w+)\[([\d,]+)\]\S* copy\(", text)
           if _ITEMSIZE.get(m.group(1), 4) * np.prod(
               [int(x) for x in m.group(2).split(",")]) >= limit]
    assert not big, big
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
        re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1))}
    assert aliased == {0}, aliased
    if name == "prefill_chunk":
        assert text.count("conditional(") == 4
        assert set(re.findall(r'ragged_dot_tiling="?([\d,]+)', text)) == {
            "128,7168,256", "128,2048,1024"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9

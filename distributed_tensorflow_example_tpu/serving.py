"""Model export for serving — the SavedModel story, TPU-native.

The reference era shipped trained models as SavedModels (graph +
variables, servable without the training code). The XLA-world
equivalent is :mod:`jax.export`: the jitted forward function is lowered
to StableHLO once, with the trained parameters baked in as constants,
and serialized to a stable, self-contained artifact that any later JAX
process (or the C++ PJRT runtime) can run WITHOUT this framework's
Python code — the same portability contract a SavedModel gave
Session.run (SURVEY.md §2.3).

Artifacts are batch-polymorphic by default: the leading batch dimension
is exported symbolically, so one artifact serves any batch size.

Layout of an export directory::

    <dir>/model.stablehlo     the serialized jax.export artifact
    <dir>/export.json         metadata: model name, input signature,
                              platforms, param count, versions
"""

from __future__ import annotations

import json
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jax_export

from .ops.moe import tile_log
from .ops.pallas.decode_attention import schedule_log

# label-side batch keys never consumed by `apply` (loss/eval only):
# pruned from the serving signature so a servable takes features only
_LABEL_KEYS = ("y", "masked_labels", "masked_weights", "__valid__")

_ARTIFACT = "model.stablehlo"
_META = "export.json"
# stepwise-generator artifacts (export_generator stepwise=True): the
# prefill and shared-decode-step programs the continuous-batching
# engine (serving_batch.py) drives, beside the monolithic artifact
_PREFILL = "prefill.stablehlo"
_DECODE = "decode.stablehlo"
# K-token speculative-verify program (export_generator spec_tokens=K,
# paged stepwise artifacts only): the engine's draft-and-verify loop
# dispatches it instead of decode.stablehlo on iterations where any
# live slot carries draft tokens
_VERIFY = "verify.stablehlo"
# chunked-prefill program (export_generator prefill_chunk=C, paged
# only): one C-token slice of a left-aligned prompt prefill, reading
# prior chunks back through the block table — the SLO scheduler
# interleaves these with shared decode steps so a long prompt can
# never stall live decoders for a whole monolithic prefill
_PREFILL_CHUNK = "prefill_chunk.stablehlo"
# the batched block step of a block-diffusion decoder (models/decoder.py):
# B lanes a slot, denoising and commit rows in one dispatch. It stands
# where decode.stablehlo stands for a one-token-a-step decoder
_BLOCK_STEP = "block_step.stablehlo"
# parameters kept as a checkpoint beside the programs (weights as
# arguments): one .npy a leaf plus params.json
_PARAMS_DIR = "params"
_PARAMS_INDEX = "params.json"
#: parameters above this many bytes cannot be StableHLO constants, once
#: per program: the artifact stores them as a checkpoint in their storage
#: dtype and every program takes the one loaded tree as an argument. A
#: rule on bytes: no flag, no model name. (The GPT family's programs keep
#: the baked form whatever their size: ROADMAP D1.)
BAKE_LIMIT_BYTES = 1 << 30


def serving_signature(batch: dict[str, Any]) -> dict[str, Any]:
    """The feature-only view of a training batch."""
    return {k: v for k, v in batch.items() if k not in _LABEL_KEYS}


def _write_artifact(out_dir: str, exported, features, params, model,
                    **extra_meta) -> str:
    """Chief-only artifact + metadata write shared by every exporter
    (one metadata schema, one serializer — exporters add their own keys
    via ``extra_meta``)."""
    artifact = os.path.join(out_dir, _ARTIFACT)
    if jax.process_index() != 0:
        # any gather the caller did was collective (all processes); the
        # artifact write is chief-only — same division as the
        # checkpoint writer
        return artifact
    os.makedirs(out_dir, exist_ok=True)
    with open(artifact, "wb") as f:
        f.write(exported.serialize())
    signature = {
        k: {"shape": list(np.shape(v)), "dtype": str(np.asarray(v).dtype)}
        for k, v in features.items()}
    with open(os.path.join(out_dir, _META), "w") as f:
        json.dump({
            "model": getattr(model, "name", type(model).__name__),
            "input_signature": signature,
            "platforms": list(exported.platforms),
            "param_count": sum(
                int(np.size(p))
                for p in jax.tree_util.tree_leaves(params)),
            "jax_version": jax.__version__,
            "calling_convention_version":
                exported.calling_convention_version,
            **extra_meta,
        }, f, indent=1)
    return artifact


def export_model(model, params, extras, out_dir: str, *,
                 sample_batch: dict[str, Any] | None = None,
                 batch_size: int = 8,
                 platforms: Sequence[str] = ("cpu", "tpu"),
                 batch_polymorphic: bool = True) -> str:
    """Serialize ``model.apply(params, extras, features, train=False)``
    with the parameters baked in; returns the artifact path.

    ``platforms`` lowers one artifact for every listed backend (the
    default covers this sandbox's CPU tests and the TPU target).
    ``batch_polymorphic`` exports the leading dimension symbolically;
    models whose COMPUTATION depends concretely on the batch size (MoE:
    expert capacity = f(token count)) cannot trace symbolically — they
    fall back to a static-batch artifact automatically (recorded in the
    metadata; the servable then accepts exactly ``batch_size``).
    """
    batch = sample_batch or model.dummy_batch(batch_size)
    features = serving_signature(batch)

    # gather to host before baking: closed-over constants must be fully
    # addressable on this process, but fsdp params span hosts (same
    # reason the checkpoint writer allgathers — ckpt/checkpoint.py
    # _to_host)
    from .ckpt.checkpoint import _to_host
    params = jax.tree_util.tree_map(_to_host, params)
    extras = jax.tree_util.tree_map(_to_host, extras)

    def serve(feats):
        logits, _ = model.apply(params, extras, feats, train=False)
        return logits

    def _export(poly: bool):
        if poly:
            specs = jax_export.symbolic_args_specs(
                (features,), "b, ...")[0]
        else:
            specs = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                               jnp.asarray(x).dtype),
                features)
        return jax_export.export(
            jax.jit(serve), platforms=list(platforms))(specs)

    # symbolic-batch traces can fail several ways: concretization (MoE
    # capacity math), inconclusive symbolic-dim comparisons, or plain
    # TypeError from Python int ops on symbolic dims
    _symbolic_failures = (jax.errors.ConcretizationTypeError, TypeError)
    _idop = getattr(jax.core, "InconclusiveDimensionOperation", None)
    if _idop is not None:
        _symbolic_failures += (_idop,)
    if batch_polymorphic:
        try:
            exported = _export(True)
        except _symbolic_failures:
            from .utils.logging import get_logger
            get_logger("serving").warning(
                "batch-polymorphic export impossible (computation "
                "depends on the batch size); exporting static batch %d "
                "— the servable accepts exactly that instance count",
                jax.tree_util.tree_leaves(features)[0].shape[0])
            batch_polymorphic = False
            exported = _export(False)
    else:
        exported = _export(False)

    return _write_artifact(out_dir, exported, features, params, model,
                           batch_polymorphic=batch_polymorphic)


#: quant metadata schema version recorded in every generator export —
#: the loader refuses artifacts claiming a NEWER schema (fields it
#: cannot validate) instead of shape-erroring deep in the scan
QUANT_SCHEMA = 1


def _normalize_weight_quant(weight_quant) -> str | None:
    """Loud CLI/export validation of the weight-quant knob: ``None`` /
    ``"off"`` -> None, ``"int8"`` -> "int8", anything else raises."""
    if weight_quant in (None, "off"):
        return None
    if weight_quant == "int8":
        return "int8"
    raise ValueError(f"weight_quant must be 'off' or 'int8', got "
                     f"{weight_quant!r}")


def _normalize_kv_cache_dtype(kv_cache_dtype, model_dtype):
    """The KV-cache storage knob: ``None``/``"auto"`` keeps the model
    compute dtype (today's behavior — the quant-off bitwise no-op),
    ``"bf16"`` stores bfloat16 explicitly, ``"int8"`` selects the
    quantized pool (paged artifacts only — the caller enforces that).
    Returns ``(np.dtype for the pool, "int8" | None)``."""
    if kv_cache_dtype in (None, "auto"):
        return np.dtype(jnp.dtype(model_dtype)), None
    if kv_cache_dtype in ("bf16", "bfloat16"):
        return np.dtype(jnp.dtype(jnp.bfloat16)), None
    if kv_cache_dtype == "int8":
        return np.dtype(np.int8), "int8"
    raise ValueError(f"kv_cache_dtype must be 'auto', 'bf16' or "
                     f"'int8', got {kv_cache_dtype!r}")


def export_generator(model, params, out_dir: str, *,
                     prompt_len: int, max_new_tokens: int,
                     batch_size: int = 1, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 0.0,
                     eos_id: int | None = None, pad_id: int = 0,
                     ragged: bool = False,
                     decode_impl: str = "stacked",
                     tokens_per_dispatch: int = 1,
                     stepwise: bool = False, slots: int = 8,
                     paged: bool = False, block_size: int = 16,
                     num_blocks: int | None = None,
                     weight_quant: str | None = None,
                     kv_cache_dtype: str | None = None,
                     pool_bytes: int | None = None,
                     spec_tokens: int = 0,
                     prefill_chunk: int = 0,
                     platforms: Sequence[str] = ("cpu", "tpu")) -> str:
    """Serialize ``model.generate`` (params baked; greedy or
    temperature/top-k/top-p sampling, optional EOS early-stop) as a
    self-contained decode artifact: the whole generation — prefill +
    the KV-cache decode loop — is ONE StableHLO program mapping
    ``{"input_ids": [B, prompt_len]}`` (plus ``"rng"`` when sampling,
    plus ``"prompt_mask"`` when ``ragged``) to ``[B, max_new_tokens]``
    token ids. Static shapes throughout (the decode loop's cache layout
    depends on prompt and generation lengths, so the artifact is
    inherently static-shape; the metadata records it as such).

    The artifact rides the decode fast path (``decode_impl="stacked"``
    + optional ``tokens_per_dispatch`` amortization — recorded in the
    metadata). Decode attention in the artifact: multi-platform
    exports, and ANY export traced off-TPU, pin the portable XLA path
    (a Mosaic custom call cannot lower for the artifact's other
    platforms — and the kernel's interpret-mode fallback on a non-TPU
    tracing host must never be baked into a TPU artifact). Only a
    TPU-only export traced ON a TPU host keeps the model's own
    (kernel-capable) setting. When sampling, the serve-time PRNG
    contract is recorded as ``prng_impl`` so the HTTP server
    synthesizes ``rng`` key data with the impl the program was traced
    under.

    ``stepwise=True`` additionally exports the TWO programs a
    continuous-batching scheduler (serving_batch.py) needs, beside the
    monolithic artifact:

    - ``prefill.stablehlo`` — one prompt ([1, prompt_len] ids + mask,
      the ragged right-pack contract) plus the whole cache pool and a
      ``slot`` index → first-token logits, the row's pad count, and
      the pool with that slot's [T, H, D] per-layer K/V slab written
      (the full slab is overwritten, so slot reuse needs no cleanup).
    - ``decode.stablehlo`` — ONE shared decode step for every slot:
      per-slot token/pos/pad/alive + pool → next-token logits [slots,
      vocab] + updated pool, riding the stacked-scan fast path with
      PER-ROW cache depths (``GPT.decode_step_batched``).

    Sampling under the scheduler is host-side per request, so the
    stepwise programs return logits (no baked temperature/rng); the
    artifact's own ``temperature``/``top_k``/``top_p``/``eos_id``
    become the scheduler's per-request DEFAULTS, and ``prng_impl`` is
    recorded for the host-side per-request keys. Slot count, prompt
    capacity, and max context are recorded under the ``stepwise``
    metadata key (static shapes — the pool is the program's working
    set, sized at export time).

    ``paged=True`` (requires ``stepwise``) exports BLOCK-PAGED stepwise
    programs instead of the slab pair: the pool is ``[L, num_blocks,
    block_size, H*D]`` shared physical blocks plus a per-slot block
    table, prefill writes whole blocks through a table row
    (left-aligned layout — see ``GPT.paged_prefill``), and the decode
    step reads/writes through ``[slots, blocks_per_slot]`` tables.
    ``num_blocks`` defaults to the slab pool's byte capacity plus the
    reserved null block (block 0 — never allocated; unused table
    entries point at it). Slab artifacts remain exportable (the
    default) as the paged path's parity oracle; ``block_size`` /
    ``num_blocks`` land in the ``stepwise`` metadata so the engine and
    bench rows can report block-level residency.

    Quantized decode (round 12):

    - ``weight_quant="int8"`` bakes the decode-path layer weights as
      symmetric per-output-channel int8 + f32 scales
      (``GPT.stack_decode_params``) into EVERY decode program of this
      export — the monolithic generation, and the stepwise/paged
      decode step — with the dequant inside the scan body, so int8 is
      what crosses HBM per layer step. Prefill stays full precision
      (it is compute-bound, and the monolithic path's prefill already
      is). LOSSY by contract: gated by greedy-drift bounds, not byte
      parity.
    - ``kv_cache_dtype="int8"`` (requires ``paged=True``) stores the
      cache pool int8 with per-token-row f32 scales in parallel
      ``cache_k_scale``/``cache_v_scale`` [L, N, Bs] pools —
      quantize-on-write in prefill and the decode step, dequant fused
      into both decode-attention impls. ``"bf16"`` stores bfloat16
      explicitly; ``"auto"`` (default) keeps the model dtype — the
      bitwise no-op.
    - ``pool_bytes`` sizes the paged pool IN BYTES: ``num_blocks`` =
      the block count whose K/V bytes fit the budget (+ the null
      block), so an int8 pool genuinely holds >= 2x the bf16 block
      count at equal bytes (the scale pools are accounted separately
      in the recorded ``block_bytes`` — ~``8/(H*D)`` relative
      overhead — and in the engine's ``bytes_resident``). Mutually
      exclusive with ``num_blocks``.

    Every generator export records ``quant_schema`` + ``weight_quant``
    (and, stepwise, ``kv_cache_dtype`` / ``kv_scale_shape``) so
    loaders can validate quant expectations loudly instead of
    shape-erroring deep in the scan.

    ``spec_tokens=K`` (K >= 2; requires ``paged=True``) additionally
    exports ``verify.stablehlo`` — the K-token speculative-verify
    program (``GPT.decode_verify_batched_paged``): per-row ``[K]``
    token inputs through the same stacked-scan fast path into the
    paged pool, returning ``[slots, K, V]`` logits, with lanes gated
    per-row by ``n_tok`` so draftless slots ride the dispatch at width
    1. Composes with ``weight_quant="int8"`` and
    ``kv_cache_dtype="int8"`` unchanged (the verify body IS the decode
    body over row-expanded inputs). ``spec_tokens`` lands in the
    ``stepwise`` metadata so the engine and the HTTP server can
    auto-detect spec capability.

    ``prefill_chunk=C`` (requires ``paged=True``; C a positive
    multiple of ``block_size``) additionally exports
    ``prefill_chunk.stablehlo`` — the C-token chunked-prefill program
    (``GPT.paged_prefill_chunk``) the SLO-aware scheduler dispatches
    instead of the monolithic prefill when ``--prefill_chunk_tokens``
    is set, interleaving prompt chunks with shared decode steps so a
    long prompt's admission can never stall live decoders for more
    than one chunk's dispatch. With a float pool the chunked byte
    stream is bit-identical to the monolithic prefill (the repo's
    standing parity discipline); the int8-pool composition rides the
    token-agreement drift gate instead. ``prefill_chunk`` lands in
    the ``stepwise`` metadata so the engine can validate the
    serve-time budget against the exported chunk width.

    A model with a ``block_step`` (a block-diffusion decoder,
    ``models/decoder.py``) exports the paged pair ``prefill.stablehlo``
    + ``block_step.stablehlo`` and no monolithic program: see
    :func:`_export_block_generator`. A model with a kind a layer and
    per-request state (``cfg.stateful``) exports
    ``prefill_chunk.stablehlo`` + ``decode.stablehlo`` over the state
    its ``state_specs`` name: see :func:`_export_state_generator`."""
    if hasattr(model, "decode_step") and model.cfg.stateful:
        refused = {"spec_tokens": spec_tokens, "weight_quant": weight_quant,
                   "temperature": temperature, "top_k": top_k,
                   "top_p": top_p,
                   "kv_cache_dtype": (kv_cache_dtype
                                      if kv_cache_dtype == "int8" else None)}
        if any(refused.values()):
            raise ValueError(
                "an artifact with per-request recurrent state takes no "
                "speculative verify program (a recurrent state cannot be "
                "rewound by position arithmetic), no int8 weights or "
                "cache, and no sampling (its programs return greedy ids, "
                f"not logits); got { {k: v for k, v in refused.items() if v} }")
        if not (stepwise and paged and prefill_chunk):
            raise ValueError(
                "a decoder with per-request recurrent state is served by "
                "the paged engine through a chunk program: export with "
                "stepwise=True, paged=True, prefill_chunk=C")
        return _export_state_generator(
            model, params, out_dir, prompt_len=prompt_len,
            max_new_tokens=max_new_tokens, slots=slots,
            block_size=block_size, num_blocks=num_blocks,
            pool_bytes=pool_bytes, prefill_chunk=prefill_chunk,
            eos_id=eos_id, pad_id=pad_id, platforms=platforms)
    if hasattr(model, "block_step"):
        refused = {"spec_tokens": spec_tokens, "weight_quant": weight_quant,
                   "prefill_chunk": prefill_chunk,
                   "kv_cache_dtype": (kv_cache_dtype
                                      if kv_cache_dtype == "int8" else None)}
        if any(refused.values()):
            raise ValueError(
                "a block-diffusion artifact takes no speculative verify "
                "program, int8 weights, int8 KV or chunked prefill (a "
                "block step is none of their programs); got "
                f"{ {k: v for k, v in refused.items() if v} }")
        if not (stepwise and paged):
            raise ValueError(
                "a block-diffusion decoder is served by the paged engine "
                "only: export with stepwise=True, paged=True")
        return _export_block_generator(
            model, params, out_dir, prompt_len=prompt_len,
            max_new_tokens=max_new_tokens, slots=slots,
            block_size=block_size, num_blocks=num_blocks,
            pool_bytes=pool_bytes, eos_id=eos_id, pad_id=pad_id,
            platforms=platforms)
    from .ckpt.checkpoint import _to_host
    params = jax.tree_util.tree_map(_to_host, params)

    weight_quant = _normalize_weight_quant(weight_quant)
    cache_dtype, kv_quant = _normalize_kv_cache_dtype(
        kv_cache_dtype, model.dtype)
    if kv_quant and not paged:
        raise ValueError(
            "kv_cache_dtype='int8' quantizes the BLOCK-PAGED pool "
            "(per-block-row scales need the paged layout) — export "
            "with paged=True, or drop the knob")
    if pool_bytes is not None:
        if not paged:
            raise ValueError("pool_bytes sizes the paged block pool "
                             "and requires paged=True")
        if num_blocks is not None:
            raise ValueError("pass pool_bytes OR num_blocks, not both "
                             "(pool_bytes derives num_blocks from the "
                             "byte budget)")
        if pool_bytes < 1:
            raise ValueError(f"pool_bytes must be >= 1, got "
                             f"{pool_bytes}")
    if spec_tokens:
        if spec_tokens < 2:
            raise ValueError(
                f"spec_tokens must be 0 (off) or >= 2 (one anchor "
                f"token + at least one draft lane per verify "
                f"dispatch), got {spec_tokens}")
        if not paged:
            raise ValueError(
                "spec_tokens exports the K-token verify program over "
                "the block-paged pool (draft rejection rewinds per-row "
                "pos through the block tables) — export with "
                "paged=True, or drop the knob")
    if prefill_chunk:
        if not paged:
            raise ValueError(
                "prefill_chunk exports the chunked-prefill program "
                "over the block-paged pool (chunks fill whole blocks "
                "through the table) — export with paged=True, or drop "
                "the knob")
        if prefill_chunk < 1 or prefill_chunk % block_size:
            raise ValueError(
                f"prefill_chunk must be a positive multiple of "
                f"block_size={block_size} (chunks tile the left-"
                f"aligned layout block-granularly), got "
                f"{prefill_chunk}")

    sampled = temperature > 0.0
    tpu_only_on_tpu = (tuple(platforms) == ("tpu",)
                       and jax.default_backend() == "tpu")
    decode_attention = ("xla" if decode_impl == "stacked"
                        and not tpu_only_on_tpu else None)

    def serve(feats):
        return model.generate(
            params, feats["input_ids"], max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, pad_id=pad_id,
            prompt_mask=feats.get("prompt_mask"),
            decode_impl=decode_impl,
            decode_attention=decode_attention,
            tokens_per_dispatch=tokens_per_dispatch,
            weight_quant=weight_quant,
            rng=(jax.random.wrap_key_data(feats["rng"])
                 if sampled else None))

    features = {"input_ids": np.zeros((batch_size, prompt_len), np.int32)}
    if ragged:
        features["prompt_mask"] = np.ones((batch_size, prompt_len),
                                          np.int32)
    if sampled:
        features["rng"] = np.zeros(
            np.shape(jax.random.key_data(jax.random.key(0))), np.uint32)
    specs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        features)
    exported = jax_export.export(
        jax.jit(serve), platforms=list(platforms))(specs)

    extra_meta = {}
    if sampled or stepwise:
        # the serve-time rng contract: key data synthesized with any
        # OTHER default impl has a different shape/meaning and would
        # surface as an opaque executable error (ADVICE r5) — record
        # the impl the trace consumed so serving_http can honor it.
        # Stepwise artifacts record it unconditionally: the scheduler
        # samples host-side with per-request keys under this impl.
        extra_meta["prng_impl"] = str(
            jax.random.key_impl(jax.random.key(0)))
    if paged and not stepwise:
        raise ValueError("paged=True exports the block-paged stepwise "
                         "programs and requires stepwise=True")
    if stepwise:
        extra_meta["stepwise"] = _export_stepwise(
            model, params, out_dir, prompt_len=prompt_len,
            max_new_tokens=max_new_tokens, slots=slots,
            decode_attention=decode_attention, platforms=platforms,
            paged=paged, block_size=block_size, num_blocks=num_blocks,
            weight_quant=weight_quant, cache_dtype=cache_dtype,
            kv_quant=kv_quant, pool_bytes=pool_bytes,
            spec_tokens=spec_tokens, prefill_chunk=prefill_chunk)
    return _write_artifact(out_dir, exported, features, params, model,
                           kind="generator", batch_polymorphic=False,
                           prompt_len=prompt_len,
                           max_new_tokens=max_new_tokens,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, eos_id=eos_id, pad_id=pad_id,
                           ragged=ragged, decode_impl=decode_impl,
                           tokens_per_dispatch=tokens_per_dispatch,
                           quant_schema=QUANT_SCHEMA,
                           weight_quant=weight_quant,
                           **extra_meta)


def _greedy_ids(logits):
    """Each row's greedy id (``[rows, V]`` -> int32 ``[rows]``, first
    index on ties, as ``np.argmax``): what a one-token program hands
    the host beside its logits, which then stay on the device unless a
    live row samples (``serving_batch.GenerationEngine``)."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _trace_and_write_stepwise(out_dir: str, prefill_fn, decode_fn,
                              prefill_specs: dict, decode_specs: dict,
                              platforms: Sequence[str],
                              base_meta: dict, verify_fn=None,
                              verify_specs: dict | None = None,
                              chunk_fn=None,
                              chunk_specs: dict | None = None,
                              **extra_meta) -> dict:
    """The shared tail of both stepwise exporters (slab and paged):
    trace + serialize the prefill/decode pair (plus the optional
    speculative-verify and chunked-prefill programs) to the canonical
    filenames (chief-only write) and assemble the ``stepwise``
    metadata block. ONE copy, so an export-flow change (donation
    hints, platform knobs, a new metadata key the engine reads)
    cannot silently diverge the two artifact kinds."""
    programs = [(_PREFILL, prefill_fn, prefill_specs),
                (_DECODE, decode_fn, decode_specs)]
    if verify_fn is not None:
        programs.append((_VERIFY, verify_fn, verify_specs))
    if chunk_fn is not None:
        programs.append((_PREFILL_CHUNK, chunk_fn, chunk_specs))
    exported, attn_schedule, returns = [], {}, {}
    for name, fn, specs in programs:
        # what each program's paged decode attention was traced with
        # (the kernel's schedule, or the XLA gather): fixed once compiled
        with schedule_log() as seen:
            exp = jax_export.export(
                jax.jit(fn), platforms=list(platforms))(specs)
        exported.append((name, exp))
        program = name.removesuffix(".stablehlo")
        if seen:
            attn_schedule[program] = seen[0] if len(seen) == 1 else seen
        # what the program hands the host beside the pool it carries:
        # the engine fetches ``ids`` where a program names them
        returns[program] = sorted(
            k for k in jax.tree_util.tree_unflatten(
                exp.out_tree, exp.out_avals)
            if not k.startswith("cache_"))
    if jax.process_index() == 0:
        os.makedirs(out_dir, exist_ok=True)
        for name, exp in exported:
            with open(os.path.join(out_dir, name), "wb") as f:
                f.write(exp.serialize())
    extra_meta["decode"] = {"returns": returns}
    if attn_schedule:
        extra_meta["decode"]["attn_schedule"] = attn_schedule
    return {**base_meta, **extra_meta}


def _export_stepwise(model, params, out_dir: str, *, prompt_len: int,
                     max_new_tokens: int, slots: int,
                     decode_attention: str | None,
                     platforms: Sequence[str], paged: bool = False,
                     block_size: int = 16,
                     num_blocks: int | None = None,
                     weight_quant: str | None = None,
                     cache_dtype=None, kv_quant: str | None = None,
                     pool_bytes: int | None = None,
                     spec_tokens: int = 0,
                     prefill_chunk: int = 0) -> dict:
    """Trace + serialize the prefill and shared-decode-step programs
    (see :func:`export_generator` ``stepwise=True``); returns the
    ``stepwise`` metadata block. Params are already host-gathered."""
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    c = model.cfg
    total = prompt_len + max_new_tokens
    if total > c.max_len:
        raise ValueError(
            f"prompt_len {prompt_len} + max_new_tokens {max_new_tokens} "
            f"exceeds max_len {c.max_len}")
    if cache_dtype is None:
        cache_dtype = np.dtype(jnp.dtype(model.dtype))

    def base_meta(pool_shape) -> dict:
        return {
            "slots": slots,
            "prompt_len": prompt_len,
            "max_new_tokens": max_new_tokens,
            "max_context": total,
            "pool_shape": list(pool_shape),
            "cache_dtype": str(cache_dtype),
            "kv_cache_dtype": ("int8" if kv_quant else str(cache_dtype)),
            "vocab_size": c.vocab_size,
        }

    if paged:
        return _export_stepwise_paged(
            model, params, out_dir, prompt_len=prompt_len,
            max_new_tokens=max_new_tokens, slots=slots,
            decode_attention=decode_attention, platforms=platforms,
            block_size=block_size, num_blocks=num_blocks,
            cache_dtype=cache_dtype, base_meta=base_meta,
            weight_quant=weight_quant, kv_quant=kv_quant,
            pool_bytes=pool_bytes, spec_tokens=spec_tokens,
            prefill_chunk=prefill_chunk)
    head_dim = c.hidden // c.heads
    pool_shape = (c.layers, slots, total, c.heads, head_dim)

    def prefill_fn(feats):
        last_h, caches, pad = model.ragged_prefill(
            params, feats["input_ids"], feats["prompt_mask"], total)
        kv = model._stack_caches(caches)        # {"k"/"v": [L,1,T,H,D]}
        slot = feats["slot"]
        ck = jax.lax.dynamic_update_slice(
            feats["cache_k"], kv["k"].astype(feats["cache_k"].dtype),
            (0, slot, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            feats["cache_v"], kv["v"].astype(feats["cache_v"].dtype),
            (0, slot, 0, 0, 0))
        logits = model.lm_logits(params, last_h[:, None])[:, 0]
        return {"logits": logits, "ids": _greedy_ids(logits),
                "pad": pad, "cache_k": ck, "cache_v": cv}

    stacked = model.stack_decode_params(params, weight_quant=weight_quant)

    def decode_fn(feats):
        logits, new = model.decode_step_batched(
            params, stacked,
            {"k": feats["cache_k"], "v": feats["cache_v"]},
            feats["tok"], feats["pos"], feats["pad"], feats["alive"],
            decode_attention=decode_attention)
        return {"logits": logits, "ids": _greedy_ids(logits),
                "cache_k": new["k"], "cache_v": new["v"]}

    pool_specs = {
        "cache_k": jax.ShapeDtypeStruct(pool_shape, cache_dtype),
        "cache_v": jax.ShapeDtypeStruct(pool_shape, cache_dtype)}
    prefill_specs = {
        "input_ids": jax.ShapeDtypeStruct((1, prompt_len), np.int32),
        "prompt_mask": jax.ShapeDtypeStruct((1, prompt_len), np.int32),
        "slot": jax.ShapeDtypeStruct((), np.int32), **pool_specs}
    decode_specs = {
        "tok": jax.ShapeDtypeStruct((slots,), np.int32),
        "pos": jax.ShapeDtypeStruct((slots,), np.int32),
        "pad": jax.ShapeDtypeStruct((slots,), np.int32),
        "alive": jax.ShapeDtypeStruct((slots,), np.int32), **pool_specs}
    return _trace_and_write_stepwise(
        out_dir, prefill_fn, decode_fn, prefill_specs, decode_specs,
        platforms, base_meta(pool_shape))


def _export_stepwise_paged(model, params, out_dir: str, *,
                           prompt_len: int, max_new_tokens: int,
                           slots: int, decode_attention: str | None,
                           platforms: Sequence[str], block_size: int,
                           num_blocks: int | None, cache_dtype,
                           base_meta, weight_quant: str | None = None,
                           kv_quant: str | None = None,
                           pool_bytes: int | None = None,
                           spec_tokens: int = 0,
                           prefill_chunk: int = 0) -> dict:
    """The block-paged stepwise pair (``export_generator``
    ``paged=True``): prefill writes a prompt's whole blocks through a
    table row, the shared decode step reads/writes through per-slot
    tables. Same artifact filenames as the slab pair — the ``paged``
    metadata key is the dispatch contract.

    ``kv_quant="int8"``: the pools are int8 with per-token-row f32
    scales in parallel ``cache_k_scale``/``cache_v_scale`` [L, N, Bs]
    pools threaded through both programs. ``pool_bytes`` derives
    ``num_blocks`` from the K/V byte budget — the lever that makes
    int8 hold 2x the bf16 block count at fixed HBM (the small scale
    pools are accounted in the recorded ``block_bytes``, not the block
    budget — ~8/(H·D) relative overhead)."""
    c = model.cfg
    total = prompt_len + max_new_tokens
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    blocks_per_slot = -(-total // block_size)
    prompt_blocks = -(-prompt_len // block_size)
    head_dim = c.hidden // c.heads
    # bytes of one block's K+V payload at the storage dtype (int8
    # itemsize 1 — exactly half of bf16, the capacity doubling)
    kv_block_bytes = 2 * c.layers * block_size * c.heads * head_dim \
        * int(np.dtype(cache_dtype).itemsize)
    # total per-block residency incl. the int8 scale rows (k+v, f32)
    block_bytes = kv_block_bytes + (
        2 * c.layers * block_size * 4 if kv_quant else 0)
    if pool_bytes is not None:
        num_blocks = 1 + pool_bytes // kv_block_bytes
    if num_blocks is None:
        # default: the slab pool's token capacity, block-granular,
        # plus the reserved null block — equal bytes, equal worst case
        num_blocks = 1 + slots * blocks_per_slot
    usable = num_blocks - 1
    if usable < blocks_per_slot:
        raise ValueError(
            f"num_blocks {num_blocks} leaves {usable} usable blocks "
            f"(block 0 is the reserved null block) but one full-depth "
            f"request needs {blocks_per_slot} blocks of {block_size} "
            "tokens — raise num_blocks or block_size"
            + (f" (pool_bytes {pool_bytes} at {kv_block_bytes} K/V "
               "bytes per block)" if pool_bytes is not None else ""))
    # a token's heads side by side (GPT's paged layout, models/gpt.py)
    pool_shape = (c.layers, num_blocks, block_size, c.heads * head_dim)
    scale_shape = (c.layers, num_blocks, block_size)

    pool_specs = {
        "cache_k": jax.ShapeDtypeStruct(pool_shape, cache_dtype),
        "cache_v": jax.ShapeDtypeStruct(pool_shape, cache_dtype)}
    if kv_quant:
        pool_specs.update({
            "cache_k_scale": jax.ShapeDtypeStruct(scale_shape,
                                                  np.float32),
            "cache_v_scale": jax.ShapeDtypeStruct(scale_shape,
                                                  np.float32)})

    def prefill_fn(feats):
        if kv_quant:
            logits, ck, cv, cks, cvs = model.paged_prefill(
                params, feats["input_ids"], feats["prompt_mask"],
                feats["cache_k"], feats["cache_v"], feats["table_row"],
                k_scale=feats["cache_k_scale"],
                v_scale=feats["cache_v_scale"])
            return {"logits": logits, "ids": _greedy_ids(logits),
                    "cache_k": ck, "cache_v": cv,
                    "cache_k_scale": cks, "cache_v_scale": cvs}
        logits, ck, cv = model.paged_prefill(
            params, feats["input_ids"], feats["prompt_mask"],
            feats["cache_k"], feats["cache_v"], feats["table_row"])
        return {"logits": logits, "ids": _greedy_ids(logits),
                "cache_k": ck, "cache_v": cv}

    stacked = model.stack_decode_params(params, weight_quant=weight_quant)

    def decode_fn(feats):
        pools = {"k": feats["cache_k"], "v": feats["cache_v"]}
        if kv_quant:
            pools.update({"k_scale": feats["cache_k_scale"],
                          "v_scale": feats["cache_v_scale"]})
        logits, new = model.decode_step_batched_paged(
            params, stacked, pools,
            feats["block_tables"], feats["tok"], feats["pos"],
            feats["pad"], feats["alive"],
            decode_attention=decode_attention)
        out = {"logits": logits, "ids": _greedy_ids(logits),
               "cache_k": new["k"], "cache_v": new["v"]}
        if kv_quant:
            out.update({"cache_k_scale": new["k_scale"],
                        "cache_v_scale": new["v_scale"]})
        return out

    prefill_specs = {
        "input_ids": jax.ShapeDtypeStruct((1, prompt_len), np.int32),
        "prompt_mask": jax.ShapeDtypeStruct((1, prompt_len), np.int32),
        "table_row": jax.ShapeDtypeStruct((prompt_blocks,), np.int32),
        **pool_specs}
    decode_specs = {
        "tok": jax.ShapeDtypeStruct((slots,), np.int32),
        "pos": jax.ShapeDtypeStruct((slots,), np.int32),
        "pad": jax.ShapeDtypeStruct((slots,), np.int32),
        "alive": jax.ShapeDtypeStruct((slots,), np.int32),
        "block_tables": jax.ShapeDtypeStruct((slots, blocks_per_slot),
                                             np.int32),
        **pool_specs}
    verify_fn = verify_specs = None
    if spec_tokens:
        def verify_fn(feats):
            pools = {"k": feats["cache_k"], "v": feats["cache_v"]}
            if kv_quant:
                pools.update({"k_scale": feats["cache_k_scale"],
                              "v_scale": feats["cache_v_scale"]})
            logits, new = model.decode_verify_batched_paged(
                params, stacked, pools,
                feats["block_tables"], feats["tok"], feats["pos"],
                feats["pad"], feats["alive"], feats["n_tok"],
                decode_attention=decode_attention)
            out = {"logits": logits, "cache_k": new["k"],
                   "cache_v": new["v"]}
            if kv_quant:
                out.update({"cache_k_scale": new["k_scale"],
                            "cache_v_scale": new["v_scale"]})
            return out

        verify_specs = {
            **{k: v for k, v in decode_specs.items() if k != "tok"},
            "tok": jax.ShapeDtypeStruct((slots, spec_tokens), np.int32),
            "n_tok": jax.ShapeDtypeStruct((slots,), np.int32)}
    chunk_fn = chunk_specs = None
    if prefill_chunk:
        # clamp the exported chunk width at the prompt capacity rounded
        # to whole blocks — a wider chunk than the prompt can ever fill
        # would only trace dead lanes
        prefill_chunk = min(prefill_chunk, prompt_blocks * block_size)

        def chunk_fn(feats):
            scales = ({"k_scale": feats["cache_k_scale"],
                       "v_scale": feats["cache_v_scale"]}
                      if kv_quant else {})
            out = model.paged_prefill_chunk(
                params, feats["input_ids"], feats["chunk_mask"],
                feats["start"], feats["cache_k"], feats["cache_v"],
                feats["table_row"], feats["chunk_blocks"], **scales)
            res = {"logits": out[0], "ids": _greedy_ids(out[0]),
                   "cache_k": out[1], "cache_v": out[2]}
            if kv_quant:
                res.update({"cache_k_scale": out[3],
                            "cache_v_scale": out[4]})
            return res

        chunk_specs = {
            "input_ids": jax.ShapeDtypeStruct((1, prefill_chunk),
                                              np.int32),
            "chunk_mask": jax.ShapeDtypeStruct((1, prefill_chunk),
                                               np.int32),
            "start": jax.ShapeDtypeStruct((), np.int32),
            "table_row": jax.ShapeDtypeStruct((prompt_blocks,),
                                              np.int32),
            "chunk_blocks": jax.ShapeDtypeStruct(
                (prefill_chunk // block_size,), np.int32),
            **pool_specs}
    quant_meta = {}
    if kv_quant:
        quant_meta = {"kv_scale_shape": list(scale_shape),
                      "kv_scale_dtype": "float32"}
    return _trace_and_write_stepwise(
        out_dir, prefill_fn, decode_fn, prefill_specs, decode_specs,
        platforms, base_meta(pool_shape),
        verify_fn=verify_fn, verify_specs=verify_specs,
        chunk_fn=chunk_fn, chunk_specs=chunk_specs,
        paged=True, block_size=block_size, num_blocks=num_blocks,
        blocks_per_slot=blocks_per_slot, prompt_blocks=prompt_blocks,
        layout="left_aligned", block_bytes=block_bytes,
        spec_tokens=spec_tokens, prefill_chunk=prefill_chunk,
        **quant_meta)


def _flat_leaves(tree, prefix: str = "") -> list[tuple[str, Any]]:
    out = []
    for k in sorted(tree):
        path = f"{prefix}/{k}" if prefix else k
        out += (_flat_leaves(tree[k], path) if isinstance(tree[k], dict)
                else [(path, tree[k])])
    return out


def save_params(directory: str, params) -> None:
    """Write a nested dict of arrays as a checkpoint: one ``.npy`` a leaf
    in its own dtype (bfloat16 as its uint16 bits: npy has no bfloat16)
    plus ``params.json``. A leaf at a time, so the host never holds the
    tree."""
    os.makedirs(directory, exist_ok=True)
    index = []
    for i, (path, leaf) in enumerate(_flat_leaves(params)):
        arr = np.asarray(leaf)
        dtype = str(arr.dtype)
        if dtype == "bfloat16":
            arr = arr.view(np.uint16)
        name = f"{i:05d}.npy"
        np.save(os.path.join(directory, name), arr)
        index.append({"path": path, "file": name, "dtype": dtype,
                      "shape": list(arr.shape)})
    with open(os.path.join(directory, _PARAMS_INDEX), "w") as f:
        json.dump(index, f)


def load_params(directory: str):
    """The checkpoint :func:`save_params` wrote, each leaf committed to
    the first device as it is read: the tree exists once, there."""
    with open(os.path.join(directory, _PARAMS_INDEX)) as f:
        index = json.load(f)
    dev = jax.devices()[0]
    tree: dict = {}
    for entry in index:
        arr = np.load(os.path.join(directory, entry["file"]))
        if entry["dtype"] == "bfloat16":
            arr = arr.view(jnp.bfloat16)
        node = tree
        *parents, last = entry["path"].split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = jax.device_put(arr, dev)
    return tree


def prefill_widths(prompt_len: int, block_size: int) -> list[int]:
    """The widths a whole-prompt prefill is exported at, widest first:
    ``prompt_len``, and its half and its quarter where they are a whole
    number of pool blocks (a prefill writes K/V in whole blocks). The
    engine admits a prompt through the narrowest that holds it: the rows
    past a prompt are computed and never read, so the mean prompt of a
    lognormal mix pays for about its own length and not for the
    longest's. A rule on the two shapes alone."""
    return [prompt_len] + [prompt_len // d for d in (2, 4)
                           if prompt_len % (d * block_size) == 0]


def _prefill_file(width: int, prompt_len: int) -> str:
    """The widest program keeps the name every artifact has."""
    return _PREFILL if width == prompt_len else f"prefill_{width}.stablehlo"


def _export_block_generator(model, params, out_dir: str, *,
                            prompt_len: int, max_new_tokens: int,
                            slots: int, block_size: int,
                            num_blocks: int | None,
                            pool_bytes: int | None, eos_id, pad_id: int,
                            platforms: Sequence[str]) -> str:
    """The artifact of a block-diffusion decoder: ``prefill.stablehlo``
    (one prompt under the block-causal mask, K/V written in whole pool
    blocks; the same program again at each narrower width of
    :func:`prefill_widths`, ``prefill_<width>.stablehlo``, listed under
    ``stepwise.prefill_widths``) and ``block_step.stablehlo`` (B lanes a
    slot; ids and confidences out, never logits), over a pool
    [L, N, Bs, KVH * D] in the model's compute dtype (a token's heads side
    by side: the layout the attention kernel reads in place,
    ``ops/pallas/decode_attention``).

    Weights: under ``BAKE_LIMIT_BYTES`` they are constants of both
    programs, as in every other artifact. Above it they are saved once,
    in their storage dtype, under ``params/``, and both programs take the
    tree as their first argument; the caller may delete its own tree
    once this returns. ``export.json`` says which (``weights``)."""
    c = model.cfg
    if slots < 1 or block_size < 1:
        raise ValueError(f"slots and block_size must be >= 1, got "
                         f"{slots}, {block_size}")
    lanes = int(c.block_length)
    if block_size % lanes:
        raise ValueError(f"block_size {block_size} must hold whole "
                         f"generation blocks of {lanes} positions")
    total = prompt_len + max_new_tokens
    if total > c.max_len:
        raise ValueError(f"prompt_len {prompt_len} + max_new_tokens "
                         f"{max_new_tokens} exceeds max_len {c.max_len}")
    # a request's last generation block may end past its max_new, never
    # past the capacity rounded up to whole generation blocks
    blocks_per_slot = -(-(-(-total // lanes) * lanes) // block_size)
    prompt_blocks = -(-prompt_len // block_size)
    cache_dtype = np.dtype(jnp.dtype(model.dtype))
    block_bytes = (2 * c.layers * block_size * c.kv_heads * c.head_dim
                   * int(cache_dtype.itemsize))
    if pool_bytes is not None and num_blocks is not None:
        raise ValueError("pass pool_bytes OR num_blocks, not both")
    if pool_bytes is not None:
        num_blocks = 1 + pool_bytes // block_bytes
    if num_blocks is None:
        num_blocks = 1 + slots * blocks_per_slot
    if num_blocks - 1 < blocks_per_slot:
        raise ValueError(
            f"num_blocks {num_blocks} leaves {num_blocks - 1} usable "
            f"blocks but one full-depth request needs {blocks_per_slot}")
    pool_shape = (c.layers, num_blocks, block_size,
                  c.kv_heads * c.head_dim)
    on_tpu = (tuple(platforms) == ("tpu",)
              and jax.default_backend() == "tpu")
    # kernels only in a TPU-only export traced on a TPU host (the rule
    # export_generator's decode_attention follows)
    prefill_attention = "flash" if on_tpu else "xla"
    step_attention = "auto" if on_tpu else "xla"

    def prefill_fn(p, feats):
        ck, cv = model.paged_prefill(
            p, feats["input_ids"], feats["prompt_mask"], feats["cache_k"],
            feats["cache_v"], feats["table_row"],
            attention=prefill_attention)
        return {"cache_k": ck, "cache_v": cv}

    def step_fn(p, feats):
        return model.block_step(
            p, feats["cache_k"], feats["cache_v"], feats["block_tables"],
            feats["tok"], feats["pos"], feats["alive"], feats["commit"],
            attention=step_attention)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    pool_specs = {"cache_k": spec(pool_shape, cache_dtype),
                  "cache_v": spec(pool_shape, cache_dtype)}
    widths = prefill_widths(prompt_len, block_size)

    def prefill_specs(width):
        return {"input_ids": spec((1, width), np.int32),
                "prompt_mask": spec((1, width), np.int32),
                "table_row": spec((-(-width // block_size),), np.int32),
                **pool_specs}

    step_specs = {"tok": spec((slots, lanes), np.int32),
                  "pos": spec((slots,), np.int32),
                  "alive": spec((slots,), np.int32),
                  "commit": spec((slots,), np.int32),
                  "block_tables": spec((slots, blocks_per_slot), np.int32),
                  **pool_specs}
    weights, param_count, param_bytes, moe = _trace_with_params(
        (*((_prefill_file(w, prompt_len), prefill_fn, prefill_specs(w))
           for w in widths),
         (_BLOCK_STEP, step_fn, step_specs)), params, platforms, out_dir)
    meta = {
        "model": getattr(model, "name", type(model).__name__),
        "kind": "generator", "batch_polymorphic": False,
        "input_signature": {"input_ids": {"shape": [1, prompt_len],
                                          "dtype": "int32"}},
        "platforms": list(platforms),
        "param_count": param_count, "param_bytes": param_bytes,
        "weights": weights,
        "jax_version": jax.__version__,
        "prompt_len": prompt_len, "max_new_tokens": max_new_tokens,
        "temperature": 0.0, "top_k": 0, "top_p": 0.0,
        "eos_id": eos_id, "pad_id": pad_id, "ragged": True,
        "prng_impl": str(jax.random.key_impl(jax.random.key(0))),
        "stepwise": {
            "slots": slots, "prompt_len": prompt_len,
            "max_new_tokens": max_new_tokens, "max_context": total,
            "pool_shape": list(pool_shape),
            "cache_dtype": str(cache_dtype),
            "kv_cache_dtype": str(cache_dtype),
            "vocab_size": c.vocab_size, "paged": True,
            "block_size": block_size, "num_blocks": num_blocks,
            "blocks_per_slot": blocks_per_slot,
            "prompt_blocks": prompt_blocks, "layout": "left_aligned",
            "block_bytes": block_bytes, "spec_tokens": 0,
            "prefill_chunk": 0,
            # the whole-prompt prefill's exported widths, widest first
            "prefill_widths": widths,
            # generation by diffusion over blocks: what the engine's
            # slot state and transfer rule need of the model
            "block": {"length": lanes, "mask_id": int(c.mask_id),
                      "denoising_steps": int(c.denoising_steps),
                      "threshold": float(c.confidence_threshold),
                      "layers": int(c.layers),
                      "experts": int(c.experts),
                      "experts_held": int(c.held),
                      "experts_per_token": int(c.experts_per_token),
                      **moe},
        },
    }
    artifact = os.path.join(out_dir, _BLOCK_STEP)
    if jax.process_index() == 0:
        with open(os.path.join(out_dir, _META), "w") as f:
            json.dump(meta, f, indent=1)
    return artifact


def _trace_with_params(fns, params, platforms, out_dir: str):
    """Export each ``(file name, fn(params, feats), feature specs)``:
    weights baked under ``BAKE_LIMIT_BYTES``, else saved once under
    ``params/`` and taken as every program's first argument. Returns
    ``(weights, param_count, param_bytes, moe)``: ``moe`` by program
    what its expert layers were traced with (``ops/moe.tile_log``), to
    keep under ``export.json``'s ``moe_tiles`` and ``moe_rows``; and,
    where a program's one-token attention logged one
    (``decode_attention.schedule_log``), ``attn_schedule`` by program."""
    leaves = jax.tree_util.tree_leaves(params)
    param_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in leaves)
    as_args = param_bytes > BAKE_LIMIT_BYTES
    chief = jax.process_index() == 0
    if chief:
        os.makedirs(out_dir, exist_ok=True)
    p_specs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    moe = {"moe_tiles": {}, "moe_rows": {}}
    for name, fn, specs in fns:
        # the tile each of the expert layer's grouped matmuls was traced
        # with and the rows they run over, of the pairs (every layer has
        # the same shapes): fixed once compiled
        rows = {}
        with tile_log(rows) as tiles, schedule_log() as seen:
            if as_args:
                exp = jax_export.export(
                    jax.jit(fn), platforms=list(platforms))(p_specs, specs)
            else:
                exp = jax_export.export(
                    jax.jit(lambda feats, fn=fn: fn(params, feats)),
                    platforms=list(platforms))(specs)
        program = name.removesuffix(".stablehlo")
        moe["moe_tiles"][program], moe["moe_rows"][program] = tiles, rows
        if seen:
            moe.setdefault("attn_schedule", {})[program] = (
                seen[0] if len(seen) == 1 else seen)
        if chief:
            with open(os.path.join(out_dir, name), "wb") as f:
                f.write(exp.serialize())
    if as_args and chief:
        save_params(os.path.join(out_dir, _PARAMS_DIR), params)
    return ("checkpoint" if as_args else "baked",
            sum(int(np.prod(x.shape)) for x in leaves), param_bytes, moe)


def _export_state_generator(model, params, out_dir: str, *,
                            prompt_len: int, max_new_tokens: int,
                            slots: int, block_size: int,
                            num_blocks: int | None,
                            pool_bytes: int | None, prefill_chunk: int,
                            eos_id, pad_id: int,
                            platforms: Sequence[str]) -> str:
    """The artifact of a decoder with a kind a layer (``models/decoder.py``,
    ``linear_attn``, ``layer_types`` or a latent alone):
    ``prefill_chunk.stablehlo`` (``prefill_chunk``
    tokens of one prompt from the state the chunks before left) and
    ``decode.stablehlo`` (one token of every slot), greedy ids out of
    both, never logits. No monolithic program and no whole-prompt
    prefill: one compiled width serves every prompt length.

    What the server keeps between dispatches is what the model's
    ``state_specs`` names, by layer kind, recorded under
    ``stepwise.state``: ``per: "block"`` arrays lie behind the block
    tables (the latent pool ``[L_mla, N, Bs, R]``, which is ALL a
    dense-latent model keeps; the index-key pool
    ``[L_full, N, Bs, D]`` of a model that selects; the K and V pools
    ``[L_full, N, Bs, KVH x D]`` of grouped-query full layers), ``per:
    "slot"`` arrays hold one row a slot (the recurrent state ``[L_kda,
    slots, H, d, d]`` float32 and the convolutions' tails; a window
    layer's ring ``[L_win, slots, ring, R]``, or its K and V rings). All
    of them are the donated
    ``cache_*`` operands of both programs, updated in place. Weights as in
    :func:`_export_block_generator`."""
    c = model.cfg
    if slots < 1 or block_size < 1:
        raise ValueError(f"slots and block_size must be >= 1, got "
                         f"{slots}, {block_size}")
    if prefill_chunk < 1 or prefill_chunk % block_size:
        raise ValueError(
            f"prefill_chunk must be a positive multiple of block_size="
            f"{block_size} (a chunk's latent rows fill whole blocks), got "
            f"{prefill_chunk}")
    total = prompt_len + max_new_tokens
    if total > c.max_len:
        raise ValueError(f"prompt_len {prompt_len} + max_new_tokens "
                         f"{max_new_tokens} exceeds max_len {c.max_len}")
    blocks_per_slot = -(-total // block_size)
    # a prompt's last chunk names whole chunks of its table row
    prompt_blocks = -(-prompt_len // prefill_chunk) * (
        prefill_chunk // block_size)
    cache_dtype = np.dtype(jnp.dtype(model.dtype))
    # what one block of a request costs: its rows of every paged array
    block_bytes = sum(
        int(np.prod([v["shape"][0], *v["shape"][2:]]))
        * np.dtype(v["dtype"]).itemsize
        for v in model.state_specs(slots=slots, num_blocks=1,
                                   block_size=block_size).values()
        if v["per"] == "block")
    if pool_bytes is not None and num_blocks is not None:
        raise ValueError("pass pool_bytes OR num_blocks, not both")
    if pool_bytes is not None:
        num_blocks = 1 + pool_bytes // max(1, block_bytes)
    if num_blocks is None:
        num_blocks = 1 + slots * blocks_per_slot
    if num_blocks - 1 < blocks_per_slot:
        raise ValueError(
            f"num_blocks {num_blocks} leaves {num_blocks - 1} usable "
            f"blocks but one full-depth request needs {blocks_per_slot}")
    specs = model.state_specs(slots=slots, num_blocks=num_blocks,
                              block_size=block_size)
    on_tpu = (tuple(platforms) == ("tpu",)
              and jax.default_backend() == "tpu")
    step_attention = "auto" if on_tpu else "xla"

    def state_of(feats):
        return {k: feats[k] for k in specs}

    def chunk_fn(p, feats):
        return model.prefill_chunk(
            p, state_of(feats), feats["input_ids"], feats["n_valid"],
            feats["start"], feats["slot"], feats["table_row"],
            feats["chunk_blocks"])

    def decode_fn(p, feats):
        return model.decode_step(
            p, state_of(feats), feats["block_tables"], feats["tok"],
            feats["pos"], feats["alive"], attention=step_attention)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))

    state_specs = {k: spec(v["shape"], v["dtype"]) for k, v in specs.items()}
    chunk_specs = {"input_ids": spec((1, prefill_chunk), np.int32),
                   "n_valid": spec((), np.int32),
                   "start": spec((), np.int32),
                   "slot": spec((), np.int32),
                   "table_row": spec((prompt_blocks,), np.int32),
                   "chunk_blocks": spec((prefill_chunk // block_size,),
                                        np.int32),
                   **state_specs}
    decode_specs = {"tok": spec((slots,), np.int32),
                    "pos": spec((slots,), np.int32),
                    "alive": spec((slots,), np.int32),
                    "block_tables": spec((slots, blocks_per_slot), np.int32),
                    **state_specs}
    weights, param_count, param_bytes, moe = _trace_with_params(
        ((_PREFILL_CHUNK, chunk_fn, chunk_specs),
         (_DECODE, decode_fn, decode_specs)), params, platforms, out_dir)
    # the first of the arrays behind the block tables
    pool_shape = next(v["shape"] for v in specs.values()
                      if v["per"] == "block")
    attn_schedule = moe.pop("attn_schedule", None)
    meta = {
        "model": getattr(model, "name", type(model).__name__),
        "kind": "generator", "batch_polymorphic": False,
        "input_signature": {"input_ids": {"shape": [1, prompt_len],
                                          "dtype": "int32"}},
        "platforms": list(platforms),
        "param_count": param_count, "param_bytes": param_bytes,
        "weights": weights, "jax_version": jax.__version__,
        "prompt_len": prompt_len, "max_new_tokens": max_new_tokens,
        "temperature": 0.0, "top_k": 0, "top_p": 0.0,
        "eos_id": eos_id, "pad_id": pad_id, "ragged": True,
        "prng_impl": str(jax.random.key_impl(jax.random.key(0))),
        "stepwise": {
            "slots": slots, "prompt_len": prompt_len,
            "max_new_tokens": max_new_tokens, "max_context": total,
            "pool_shape": list(pool_shape),
            "cache_dtype": str(cache_dtype),
            "kv_cache_dtype": str(cache_dtype),
            "vocab_size": c.vocab_size, "paged": True,
            "block_size": block_size, "num_blocks": num_blocks,
            "blocks_per_slot": blocks_per_slot,
            "prompt_blocks": prompt_blocks, "layout": "left_aligned",
            "block_bytes": block_bytes, "spec_tokens": 0,
            "prefill_chunk": prefill_chunk,
            # a kind a layer: what the engine allocates, zeroes, carries
            # and releases, and what it refuses (see GenerationEngine)
            "state": {"specs": specs,
                      "mixers": [c.mixer(i) for i in range(c.layers)],
                      "ffns": ["dense" if i < c.dense_layers else "moe"
                               for i in range(c.layers)],
                      "layers": int(c.layers),
                      "experts": int(c.experts),
                      "experts_held": int(c.held),
                      "experts_per_token": int(c.experts_per_token),
                      "vocab_held": int(c.vocab),
                      "first_vocab": int(c.first_vocab),
                      # rows a selecting layer attends to, rows a window
                      # layer sees (0: the model has no such layer)
                      "index_topk": int(c.index_topk),
                      "window": int(c.window),
                      # groups the router's choice is limited by (1:
                      # over all experts): such programs hand the host
                      # ``routed_rows`` beside ``expert_rows``
                      "expert_groups": int(c.expert_groups),
                      "top_expert_groups": int(c.top_expert_groups),
                      **moe},
        },
    }
    if attn_schedule:
        # what each program's one-token attention was traced with, where
        # GPT-2's artifacts keep theirs
        meta["stepwise"]["decode"] = {"attn_schedule": attn_schedule}
    artifact = os.path.join(out_dir, _DECODE)
    if jax.process_index() == 0:
        with open(os.path.join(out_dir, _META), "w") as f:
            json.dump(meta, f, indent=1)
    return artifact


def validate_quant_meta(meta: dict, *, where: str = "artifact") -> None:
    """Loud load-time validation of an artifact's quantization
    metadata — every mismatch names the ``export.json`` field instead
    of shape-erroring deep inside the scan. Artifacts predating the
    quant schema (no ``quant_schema`` key) pass untouched: they carry
    no quant features (callers may count them via
    ``serving_quant_fallback_total``)."""
    schema = meta.get("quant_schema")
    if schema is None:
        return
    if not isinstance(schema, int) or schema < 1 or schema > QUANT_SCHEMA:
        raise ValueError(
            f"{where}: metadata field 'quant_schema'={schema!r} is not "
            f"supported by this loader (understands 1..{QUANT_SCHEMA}) "
            "— re-export the artifact or upgrade the server")
    wq = meta.get("weight_quant")
    if wq not in (None, "int8"):
        raise ValueError(
            f"{where}: metadata field 'weight_quant'={wq!r} names an "
            "unknown weight quantization (known: null, 'int8')")
    sm = meta.get("stepwise")
    if not sm:
        return
    kd = sm.get("kv_cache_dtype", sm.get("cache_dtype"))
    if kd == "int8":
        if not sm.get("paged"):
            raise ValueError(
                f"{where}: metadata field 'stepwise.kv_cache_dtype'="
                "'int8' requires a paged artifact ('stepwise.paged' is "
                "false) — the int8 pool's scale rows ride the block "
                "layout")
        want = [sm["pool_shape"][i] for i in (0, 1, 2)]   # [L, N, Bs]
        got = sm.get("kv_scale_shape")
        if got != want:
            raise ValueError(
                f"{where}: metadata field 'stepwise.kv_scale_shape'="
                f"{got!r} does not match the per-token-row layout "
                f"{want} implied by 'stepwise.pool_shape'="
                f"{sm['pool_shape']}")
        sd = sm.get("kv_scale_dtype", "float32")
        try:
            np.dtype(sd)
        except TypeError as e:
            raise ValueError(
                f"{where}: metadata field 'stepwise.kv_scale_dtype'="
                f"{sd!r} is not a dtype: {e}") from e
    elif kd is not None:
        try:
            np.dtype(kd)
        except TypeError as e:
            raise ValueError(
                f"{where}: metadata field 'stepwise.kv_cache_dtype'="
                f"{kd!r} is not a dtype (or 'int8'): {e}") from e


class ServableModel:
    """A loaded export: ``servable(features) -> logits``.

    Runs the deserialized StableHLO artifact — the training framework's
    model code is NOT needed (and not consulted)."""

    def __init__(self, directory: str):
        with open(os.path.join(directory, _META)) as f:
            self.meta = json.load(f)
        validate_quant_meta(self.meta, where=directory)
        path = os.path.join(directory, _ARTIFACT)
        sm = self.meta.get("stepwise") or {}
        if not os.path.exists(path) and (sm.get("block")
                                         or sm.get("state")):
            # a block-diffusion or per-request-state artifact has no
            # monolithic program: the scheduler's pair is all of it
            self._exported = None
            self._call = self._scheduler_only
            return
        with open(path, "rb") as f:
            self._exported = jax_export.deserialize(f.read())
        self._call = jax.jit(self._exported.call)

    @staticmethod
    def _scheduler_only(features):
        raise ValueError(
            "this artifact holds no monolithic program (it generates by "
            "diffusion over blocks, or keeps per-request state): serve it "
            "with the scheduler on (the default for stepwise artifacts)")

    @property
    def input_signature(self) -> dict:
        return self.meta["input_signature"]

    def __call__(self, features: dict[str, Any]):
        return self._call(features)


def load_servable(directory: str) -> ServableModel:
    return ServableModel(directory)


def has_stepwise(directory: str) -> bool:
    """True when ``directory`` holds the stepwise (prefill + shared
    decode step) artifacts a continuous-batching scheduler can drive."""
    def has(name):
        return os.path.exists(os.path.join(directory, name))

    return ((has(_PREFILL) or has(_PREFILL_CHUNK))
            and (has(_DECODE) or has(_BLOCK_STEP)))


class StepwiseGenerator:
    """A loaded stepwise generator export: the prefill and shared
    decode-step programs plus their metadata, for the
    continuous-batching engine (serving_batch.GenerationEngine).

    Like :class:`ServableModel`, runs the deserialized StableHLO only —
    the model code is not consulted. The cache pool rides through both
    calls as jax arrays; both jits DONATE their inputs so the pool is
    updated in place where the backend supports aliasing (the pool is
    the only multi-megabyte operand, and the caller always replaces its
    reference with the returned pool)."""

    def __init__(self, directory: str):
        with open(os.path.join(directory, _META)) as f:
            self.meta = json.load(f)
        step_meta = self.meta.get("stepwise")
        if not step_meta or not has_stepwise(directory):
            raise ValueError(
                f"{directory!r} holds no stepwise generator artifacts — "
                "re-export with export_generator(..., stepwise=True) "
                "(or serve it with the scheduler off)")
        validate_quant_meta(self.meta, where=directory)
        self.step_meta = step_meta
        #: block-paged artifacts ([L, N, Bs, H*D] pool + block tables)
        #: vs the slab pair ([L, slots, T, H, D]) — the engine branches
        #: its allocator/prefix-cache machinery on this
        self.paged: bool = bool(step_meta.get("paged", False))
        #: "int8" for the quantized pool (parallel scale pools ride
        #: along in make_pool/_split), else the storage float dtype
        self.kv_cache_dtype: str = str(
            step_meta.get("kv_cache_dtype", step_meta["cache_dtype"]))
        #: K of the exported speculative-verify program (0 = the export
        #: carries none — the engine must run spec-off)
        self.spec_tokens: int = int(step_meta.get("spec_tokens", 0))
        verify_path = os.path.join(directory, _VERIFY)
        if self.spec_tokens and not os.path.exists(verify_path):
            raise ValueError(
                f"{directory!r} metadata claims spec_tokens="
                f"{self.spec_tokens} but {_VERIFY} is missing — the "
                "export is torn; re-export with export_generator(..., "
                f"spec_tokens={self.spec_tokens})")
        #: C of the exported chunked-prefill program (0 = none — the
        #: engine must run with chunking off)
        self.prefill_chunk_tokens: int = int(
            step_meta.get("prefill_chunk", 0))
        chunk_path = os.path.join(directory, _PREFILL_CHUNK)
        if self.prefill_chunk_tokens and not os.path.exists(chunk_path):
            raise ValueError(
                f"{directory!r} metadata claims prefill_chunk="
                f"{self.prefill_chunk_tokens} but {_PREFILL_CHUNK} is "
                "missing — the export is torn; re-export with "
                "export_generator(..., prefill_chunk="
                f"{self.prefill_chunk_tokens})")
        #: generation by diffusion over blocks: the artifact's ``block``
        #: metadata (length, mask id, schedule), else None. Such an
        #: artifact's step program is block_step.stablehlo
        self.block: dict | None = step_meta.get("block")
        #: a kind a layer: the artifact's ``state`` metadata (the state
        #: specs by layer kind, :func:`_export_state_generator`), else
        #: None. Such an artifact has no whole-prompt prefill program:
        #: prompts go through ``prefill_chunk``
        self.state: dict | None = step_meta.get("state")
        #: what each program's paged decode attention was traced with
        #: (``ops.pallas.decode_attention.schedule_log``), by program
        self.attn_schedule: dict = (step_meta.get("decode") or {}).get(
            "attn_schedule", {})
        #: by program, the names of what it hands the host beside the
        #: pool ({} for an artifact exported before this was recorded:
        #: its steps hand logits, and are served so)
        self.returns: dict = (step_meta.get("decode") or {}).get(
            "returns", {})
        #: the one loaded parameter tree of a weights-as-arguments
        #: artifact (``weights: "checkpoint"``), which every program
        #: takes, never donated; None where the weights are baked
        self.params = None
        if self.meta.get("weights") == "checkpoint":
            self.params = load_params(os.path.join(directory, _PARAMS_DIR))
        #: the whole-prompt prefill's exported widths, widest first (an
        #: artifact that does not list them has one, ``prompt_len``; a
        #: per-request-state artifact none)
        prompt_len = int(step_meta["prompt_len"])
        self.prefill_widths: tuple[int, ...] = () if self.state else tuple(
            int(w) for w in step_meta.get("prefill_widths", [prompt_len]))
        prefill_exps = {}
        for w in self.prefill_widths:
            with open(os.path.join(
                    directory, _prefill_file(w, prompt_len)), "rb") as f:
                prefill_exps[w] = jax_export.deserialize(f.read())
        with open(os.path.join(
                directory, _BLOCK_STEP if self.block else _DECODE),
                "rb") as f:
            self._decode_exp = jax_export.deserialize(f.read())
        self._verify_exp = None
        if self.spec_tokens:
            with open(verify_path, "rb") as f:
                self._verify_exp = jax_export.deserialize(f.read())
        self._chunk_exp = None
        if self.prefill_chunk_tokens:
            with open(chunk_path, "rb") as f:
                self._chunk_exp = jax_export.deserialize(f.read())
        # donate ONLY the pool (the multi-megabyte operand): donating
        # the whole feature dict would warn per-call about the small
        # int arrays XLA can't alias into the outputs
        # each wrapper carries its program's name, so the executed
        # programs read jit_prefill / jit_decode / jit_verify /
        # jit_prefill_chunk in a profiler capture and in compile events
        # (the exported artifacts are untouched); a prefill of any width
        # is jit_prefill
        def split(call, name):
            if self.params is not None:
                return split_with_params(call, name)

            def fn(pool, rest):
                return call({**rest, **pool})
            fn.__name__ = fn.__qualname__ = name
            return jax.jit(fn, donate_argnums=(0,))

        def split_with_params(call, name):
            def fn(pool, params, rest):
                return call(params, {**rest, **pool})
            fn.__name__ = fn.__qualname__ = name
            jitted = jax.jit(fn, donate_argnums=(0,))
            return lambda pool, rest: jitted(pool, self.params, rest)

        self._prefills = {w: split(exp.call, "prefill")
                          for w, exp in prefill_exps.items()}
        self._prefill = self._prefills.get(prompt_len)      # the widest
        self._zero = None
        per_slot = [k for k, v in (self.state or {}).get(
            "specs", {}).items() if v["per"] == "slot"]
        if per_slot:

            def zero_slot(pool, slot):
                return {k: (v.at[:, slot].set(0) if k in per_slot else v)
                        for k, v in pool.items()}
            self._zero = jax.jit(zero_slot, donate_argnums=(0,))
        self._decode = split(self._decode_exp.call,
                             "block_step" if self.block else "decode")
        self._verify = (split(self._verify_exp.call, "verify")
                        if self._verify_exp is not None else None)
        self._chunk = (split(self._chunk_exp.call, "prefill_chunk")
                       if self._chunk_exp is not None else None)

    def make_pool(self) -> dict:
        """A zeroed cache pool of the exported shape (the engine's
        one-time allocation) — int8 artifacts include the parallel
        per-token-row scale pools."""
        m = self.step_meta
        if self.state:
            dev = jax.devices()[0]
            return {k: jax.device_put(
                        jnp.zeros(tuple(v["shape"]), np.dtype(v["dtype"])),
                        dev)
                    for k, v in self.state["specs"].items()}
        shape = tuple(m["pool_shape"])
        dtype = np.dtype(m["cache_dtype"])
        # COMMITTED to its device, like every pool the programs hand
        # back: an uncommitted jnp.zeros pool keys the jit cache apart
        # from the returned one, and the second request ever served
        # then pays a second full compile of the prefill program (33 s
        # of time-to-first-token at GPT-2-small on the chip)
        dev = jax.devices()[0]

        def zeros(shape, dtype):
            return jax.device_put(jnp.zeros(shape, dtype), dev)

        pool = {"cache_k": zeros(shape, dtype),
                "cache_v": zeros(shape, dtype)}
        if self.kv_cache_dtype == "int8":
            sshape = tuple(m["kv_scale_shape"])
            sdtype = np.dtype(m.get("kv_scale_dtype", "float32"))
            pool.update({"cache_k_scale": zeros(sshape, sdtype),
                         "cache_v_scale": zeros(sshape, sdtype)})
        return pool

    @staticmethod
    def _split(feats: dict) -> tuple[dict, dict]:
        # every cache_* operand (K/V pools + int8 scale pools) is part
        # of the donated pool group; the small int arrays are not
        pool = {k: v for k, v in feats.items()
                if k.startswith("cache_")}
        rest = {k: v for k, v in feats.items()
                if not k.startswith("cache_")}
        return pool, rest

    def prefill(self, feats: dict) -> dict:
        """The whole-prompt prefill at the width of ``input_ids``, one of
        :attr:`prefill_widths`."""
        if not self._prefills:
            raise ValueError("this artifact holds no whole-prompt prefill "
                             "program: its prompts go through "
                             "prefill_chunk")
        pool, rest = self._split(feats)
        return self._prefills[rest["input_ids"].shape[1]](pool, rest)

    def zero_slot(self, pool: dict, slot: int) -> dict:
        """The pool with slot ``slot``'s rows of every ``per: "slot"``
        array zeroed (in place: the pool is donated): what a request
        that takes the slot starts from."""
        if self._zero is None:
            raise ValueError("this artifact keeps no per-slot state "
                             "(nothing to zero: a row behind the block "
                             "tables is written before it is read)")
        return self._zero(pool, np.int32(slot))

    def decode(self, feats: dict) -> dict:
        pool, rest = self._split(feats)
        return self._decode(pool, rest)

    @staticmethod
    def committed(array):
        """``array`` COMMITTED to the pool's device, as everything a
        program returns is: a step fed the ids the step before it left
        on the device and a step fed the host's own are then ONE
        compiled program (an uncommitted operand keys the jit cache
        apart from a committed one: see :meth:`make_pool`)."""
        return jax.device_put(array, jax.devices()[0])

    def block_step(self, feats: dict) -> dict:
        """One batched block step of a block-diffusion artifact (``tok``
        [slots, B], ``pos``/``alive``/``commit`` [slots],
        ``block_tables``): ``ids``/``conf`` [slots, B], the routing
        scalars and the pool."""
        if self.block is None:
            raise ValueError("this artifact holds no block-step program "
                             "(it decodes one token a step)")
        return self.decode(feats)

    def verify(self, feats: dict) -> dict:
        """The K-token speculative-verify dispatch (``tok`` is
        [slots, spec_tokens]; adds ``n_tok`` [slots]) — only on
        artifacts exported with ``spec_tokens >= 2``."""
        if self._verify is None:
            raise ValueError(
                "this artifact was exported without a verify program "
                "(spec_tokens=0) — re-export with export_generator("
                "..., spec_tokens=K) to enable speculative decoding")
        pool, rest = self._split(feats)
        return self._verify(pool, rest)

    def prefill_chunk(self, feats: dict) -> dict:
        """One C-token chunked-prefill dispatch (``input_ids``/
        ``chunk_mask`` [1, C] + ``start``/``table_row``/
        ``chunk_blocks``) — only on artifacts exported with
        ``prefill_chunk=C``."""
        if self._chunk is None:
            raise ValueError(
                "this artifact was exported without a chunked-prefill "
                "program (prefill_chunk=0) — re-export with "
                "export_generator(..., prefill_chunk=C) to enable "
                "chunked prefill")
        pool, rest = self._split(feats)
        return self._chunk(pool, rest)


def load_stepwise(directory: str) -> StepwiseGenerator:
    return StepwiseGenerator(directory)

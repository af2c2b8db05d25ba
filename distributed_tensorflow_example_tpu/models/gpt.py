"""GPT-style causal language model + KV-cache autoregressive decoding.

No decoder-only model exists in the reference (MLP/CNN era — SURVEY.md
§2.5/§5.7); this family exists because a framework claiming transformer
coverage needs the *causal* half of the design space: causal attention
masks, next-token training, and the TPU-native autoregressive inference
pattern (static-shape KV cache advanced by ``lax.scan`` +
``dynamic_update_slice`` — the decode loop that cannot be expressed as
"just call the trainer again").

Architecture (GPT-2 layout): learned token + position embeddings, pre-LN
blocks (``h += attn(ln1(h)); h += ffn(ln2(h))``), final layernorm, LM
head weight-tied to the token embedding. Causal masking rides the shared
:func:`~..ops.attention.multi_head_attention` ``causal=True`` path (xla
and flash impls both support it).

TPU-first notes:

- bf16 matmuls / f32 softmax+LN, static shapes (same recipe as Bert).
- Megatron TP via ``sharding_rules`` (QKV/FFN-in column-split, O/FFN-out
  row-split, vocab-sharded tied embedding) — the same rule shapes as
  Bert, so TP/fsdp/data compose identically.
- ``generate``: prefill runs ONE full causal forward over the prompt
  (MXU-dense), then the decode loop is a single compiled ``lax.scan``
  whose carry is the static-shape [B, T, H, D] per-layer KV cache —
  no per-token retrace, no dynamic shapes, one dispatch for the whole
  generation.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..config import TrainConfig, flash_attention_kwargs, lm_loss_settings
from ..ops import losses, nn
from ..ops.attention import multi_head_attention
from ..parallel.mesh import AxisNames
from ..parallel.sharding import ShardingRules
from .base import cast_floating, register_model, resolve_dtype
from .bert import REMAT_POLICIES


def quantize_kv_rows(x):
    """Symmetric per-row int8 quantization of K/V entries: ``x``
    [..., H*D] (a token's heads side by side, as the paged pool holds
    them) -> ``(q int8 [..., H*D], scale f32 [...])`` with
    ``scale = max|row| / 127`` over each token row's H*D values (eps
    floor so an all-zero row dequantizes to exact zeros instead of
    NaN). Deterministic in the row values alone — the property the
    prefix cache's byte-identity contract rides: the same token prefix
    always produces the same int8 block bytes, whether written by
    prefill or by a teacher-forced decode step."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.round(xf / scale[..., None]).astype(jnp.int8)
    return q, scale


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 30522       # framework default vocab (BERT wordpiece)
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    intermediate: int = 3072
    max_len: int = 1024
    dropout: float = 0.1
    #: LM-loss execution strategy (ops/losses.py lm_head_xent). The
    #: [B, S, vocab] logits tensor is the memory wall of causal-LM
    #: training (b64 s512 at the 30k vocab is ~4 GB of f32 logits —
    #: measured OOM on the v5e chip) AND ~21 ms of the 170 ms gpt_small
    #: step (BASELINE.md "Vocab chain"): "full" materializes it (the
    #: parity oracle and kill switch), "chunked" bounds residency at
    #: [B, loss_chunk, vocab] via jax.checkpoint recompute (the legacy
    #: escape hatch, now the fallback), "fused" never builds it in
    #: either direction (blockwise vocab scan + custom VJP) and gets
    #: token_accuracy from the same pass.
    loss_impl: str = "full"
    #: seq chunk for loss_impl="chunked" (must divide seq_len; > 0
    #: with loss_impl="full" is accepted as the legacy spelling of
    #: "chunked" — the pre-round-7 --lm_loss_chunk contract).
    loss_chunk: int = 0
    #: vocab tile for loss_impl="fused" (0 = losses.DEFAULT_VOCAB_BLOCK;
    #: swept by experiments/vocab_chain_sweep.py).
    loss_vocab_block: int = 0

    @classmethod
    def small(cls) -> "GPTConfig":
        """GPT-2-small shape (124M at its native 50k vocab)."""
        return cls()

    @classmethod
    def tiny(cls) -> "GPTConfig":
        return cls(vocab_size=1000, hidden=128, layers=2, heads=4,
                   intermediate=256, max_len=128)


class GPT:
    name = "gpt"

    def __init__(self, cfg: GPTConfig, dtype=jnp.float32,
                 attention_impl: str = "xla", attention_fn=None,
                 param_dtype=jnp.float32, remat: str = "none",
                 decode_attention_impl: str = "auto",
                 attention_kwargs: dict | None = None,
                 accuracy_every_n: int = 1):
        assert cfg.hidden % cfg.heads == 0
        if remat != "none" and remat not in REMAT_POLICIES:
            raise ValueError(f"remat must be one of "
                             f"{['none', *REMAT_POLICIES]}, got {remat!r}")
        if decode_attention_impl not in ("auto", "pallas", "xla"):
            raise ValueError(f"decode_attention_impl must be auto/pallas/"
                             f"xla, got {decode_attention_impl!r}")
        # LM-loss lever validation, loud at model build (config-built
        # models are additionally validated by config.lm_loss_settings
        # before any trace):
        if cfg.loss_impl not in losses.LM_LOSS_IMPLS:
            raise ValueError(
                f"lm_loss_impl must be one of {losses.LM_LOSS_IMPLS}, "
                f"got {cfg.loss_impl!r}")
        if cfg.loss_chunk < 0:
            raise ValueError(
                f"lm_loss_chunk={cfg.loss_chunk} must be >= 0")
        if cfg.loss_vocab_block < 0:
            raise ValueError(f"lm_loss_vocab_block={cfg.loss_vocab_block} "
                             "must be >= 0")
        if cfg.loss_chunk and cfg.loss_impl == "full":
            # legacy spelling: loss_chunk alone meant "chunked" before
            # the impl knob existed — honor it rather than silently
            # ignoring the chunk (the knob's whole point is not OOMing)
            cfg.loss_impl = "chunked"
        if cfg.loss_impl == "chunked" and not cfg.loss_chunk:
            raise ValueError("lm_loss_impl='chunked' needs lm_loss_chunk "
                             "> 0 (the chunk size)")
        if cfg.loss_impl == "fused" and cfg.loss_chunk:
            raise ValueError(
                "lm_loss_chunk conflicts with lm_loss_impl='fused': the "
                "fused vocab scan never materializes full logits, so "
                "there is nothing for the seq-chunk recompute to bound "
                "— drop --lm_loss_chunk (or pick impl='chunked')")
        if cfg.loss_vocab_block and cfg.loss_impl != "fused":
            raise ValueError(
                f"lm_loss_vocab_block={cfg.loss_vocab_block} tunes the "
                f"fused vocab scan and requires lm_loss_impl='fused', "
                f"got {cfg.loss_impl!r}")
        if accuracy_every_n < 1:
            raise ValueError(f"token_accuracy_every_n={accuracy_every_n} "
                             "must be >= 1")
        if accuracy_every_n != 1 and cfg.loss_impl == "fused":
            # same loud contract as config.lm_loss_settings, for direct
            # (non-config) construction: fused's accuracy is free, so
            # the cadence knob would be silently inert
            raise ValueError(
                f"token_accuracy_every_n={accuracy_every_n} skips the "
                "full/chunked paths' per-step argmax; lm_loss_impl="
                "'fused' computes accuracy inside the same vocab scan "
                "at no extra cost — drop the knob")
        #: cadence of the per-step token_accuracy argmax on the
        #: full/chunked paths (1 = every step; the fused path's argmax
        #: is free and ignores this). n > 1 keeps a step counter in
        #: TrainState.extras and skips the argmax on non-multiple steps
        #: (token_accuracy then reads -1.0 — the skipped-metric
        #: sentinel). Does NOT compose with microbatch accumulation
        #: (the loss runs per microbatch and the metric mean would
        #: blend real accuracies with the sentinel) —
        #: config.lm_loss_settings rejects that combination.
        self.accuracy_every_n = accuracy_every_n
        self.cfg = cfg
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.attention_impl = attention_impl
        # flash-kernel tuning levers (block sizes / bwd variant), already
        # validated by config.flash_attention_kwargs when built from a
        # TrainConfig; {} = kernel defaults
        self.attention_kwargs = dict(attention_kwargs or {})
        # decode fast path: single-query Pallas attention over the cache
        # slab ("auto" = kernel on TPU at tile-friendly shapes, XLA
        # otherwise; see ops/pallas/decode_attention.py)
        self.decode_attention_impl = decode_attention_impl
        # sequence parallelism: pass make_ring_attention(mesh, causal=True)
        # — the ring schedule's causal block masking (global q/k offsets
        # per hop) makes the sharded result equal the single-device
        # causal attention; asserted in tests/test_gpt.py
        self.attention_fn = attention_fn
        self.remat = remat
        self.head_dim = cfg.hidden // cfg.heads

    def _maybe_remat(self, fn):
        if self.remat == "none":
            return fn
        return jax.checkpoint(fn, policy=REMAT_POLICIES[self.remat])

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array):
        c = self.cfg
        keys = iter(jax.random.split(rng, 2 + c.layers * 6))
        params: dict = {
            "wte": nn.embedding_init(next(keys), c.vocab_size, c.hidden),
            "wpe": nn.embedding_init(next(keys), c.max_len, c.hidden),
        }
        for i in range(c.layers):
            params[f"layer_{i}"] = {
                "ln1": nn.layernorm_init(c.hidden),
                "attn": {
                    "q": nn.dense_init(next(keys), c.hidden, c.hidden,
                                       init="glorot"),
                    "k": nn.dense_init(next(keys), c.hidden, c.hidden,
                                       init="glorot"),
                    "v": nn.dense_init(next(keys), c.hidden, c.hidden,
                                       init="glorot"),
                    "o": nn.dense_init(next(keys), c.hidden, c.hidden,
                                       init="glorot"),
                },
                "ln2": nn.layernorm_init(c.hidden),
                "ffn": {
                    "in": nn.dense_init(next(keys), c.hidden,
                                        c.intermediate, init="glorot"),
                    "out": nn.dense_init(next(keys), c.intermediate,
                                         c.hidden, init="glorot"),
                },
            }
        params["ln_f"] = nn.layernorm_init(c.hidden)
        params = cast_floating(params, self.param_dtype)
        if self.accuracy_every_n != 1:
            # the every-n accuracy cadence needs a step counter the loss
            # can read; extras is the framework slot for exactly this
            # kind of non-trained state (f32 so shard_map's extras
            # pmean is exact — equal values on every replica). NOTE the
            # counter is part of the checkpoint layout: flipping the
            # knob ON over an existing run's ckpt_dir fails loudly at
            # restore ("checkpoint missing leaf extras/lm_step") —
            # set it from the first step of a run, not mid-flight
            return params, {"lm_step": jnp.zeros((), jnp.float32)}
        return params

    # ------------------------------------------------------------------
    def _qkv(self, ap, h):
        b, s, _ = h.shape

        def split(x):
            return x.reshape(b, s, self.cfg.heads, self.head_dim)

        return (split(nn.dense(ap["q"], h, dtype=self.dtype)),
                split(nn.dense(ap["k"], h, dtype=self.dtype)),
                split(nn.dense(ap["v"], h, dtype=self.dtype)))

    def _ffn(self, lp, x):
        f = nn.dense(lp["ffn"]["in"], x, dtype=self.dtype)
        f = jax.nn.gelu(f.astype(jnp.float32)).astype(self.dtype)
        return nn.dense(lp["ffn"]["out"], f, dtype=self.dtype)

    def _layer(self, lp, h, mask, lrng, *, train: bool,
               use_dropout: bool, return_kv: bool = False):
        """Pre-LN decoder block (full-sequence causal path). ONE body for
        training and prefill: ``return_kv`` additionally yields this
        layer's (k, v) so the decode cache is filled by the exact same
        computation the oracle runs — an architecture tweak here cannot
        diverge the cached path."""
        c = self.cfg
        b, s, _ = h.shape
        q, k, v = self._qkv(lp["attn"], nn.layernorm(lp["ln1"], h))
        if self.attention_fn is not None:
            ctx = self.attention_fn(q, k, v, mask=mask, causal=True)
        else:
            ctx = multi_head_attention(
                q, k, v, mask=mask[:, None, None, :], causal=True,
                impl=self.attention_impl,
                flash_kwargs=self.attention_kwargs or None)
        a = nn.dense(lp["attn"]["o"], ctx.reshape(b, s, c.hidden),
                     dtype=self.dtype)
        if use_dropout:
            a = nn.dropout(jax.random.fold_in(lrng, 1), a, c.dropout,
                           train=True)
        h = h + a.astype(h.dtype)
        f = self._ffn(lp, nn.layernorm(lp["ln2"], h))
        if use_dropout:
            f = nn.dropout(jax.random.fold_in(lrng, 2), f, c.dropout,
                           train=True)
        h = h + f.astype(h.dtype)
        return (h, (k, v)) if return_kv else h

    def _embed(self, params, ids, pos_ids, rng, train):
        c = self.cfg
        h = (nn.embedding(params["wte"], ids)
             + nn.embedding(params["wpe"], pos_ids))
        h = h.astype(self.dtype)
        use_dropout = train and c.dropout > 0 and rng is not None
        if use_dropout:
            h = nn.dropout(jax.random.fold_in(rng, 1000), h, c.dropout,
                           train=True)
        return h, use_dropout

    def encode(self, params, batch, rng=None, train: bool = False):
        c = self.cfg
        ids = batch["input_ids"]
        _, s = ids.shape
        mask = batch.get("attention_mask", jnp.ones_like(ids))
        h, use_dropout = self._embed(
            params, ids, jnp.arange(s, dtype=jnp.int32)[None], rng, train)
        layer = self._maybe_remat(
            functools.partial(self._layer, train=train,
                              use_dropout=use_dropout))
        for i in range(c.layers):
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            h = layer(params[f"layer_{i}"], h, mask, lrng)
        return nn.layernorm(params["ln_f"], h)

    def lm_logits(self, params, h):
        """Weight-tied LM head: [B,S,hid] -> [B,S,V] f32 logits."""
        table = params["wte"]["table"]
        logits = jnp.einsum("bsh,vh->bsv", h.astype(self.dtype),
                            table.astype(self.dtype),
                            preferred_element_type=jnp.float32)
        return logits

    def apply(self, params, extras, batch, rng=None, train: bool = False):
        return self.lm_logits(
            params, self.encode(params, batch, rng, train)), extras

    # ------------------------------------------------------------------
    def _lm_loss(self, params, h, targets, w, *, accuracy: bool = True):
        """Next-token loss + accuracy over encoded ``h`` [B, S, hid].
        ``targets``/``w`` are the S-1 shifted labels/weights; ONE setup
        pads them with a weight-0 dummy at position S-1 so every impl
        (full / chunked / fused — ops/losses.py lm_head_xent, the shared
        blockwise core) sees the same aligned [B, S] arrays. Returns
        (loss, accuracy) as weighted token means."""
        c = self.cfg
        targets = jnp.concatenate(
            [targets, jnp.zeros_like(targets[:, :1])], axis=1)
        w = jnp.concatenate([w, jnp.zeros_like(w[:, :1])], axis=1)
        return losses.lm_head_xent(
            h, params["wte"]["table"], targets, w, impl=c.loss_impl,
            seq_chunk=c.loss_chunk, vocab_block=c.loss_vocab_block,
            dtype=self.dtype, accuracy=accuracy)

    def loss(self, params, extras, batch, rng):
        # next-token prediction: position t predicts token t+1; padding
        # (attention_mask == 0) carries no loss
        targets = batch["input_ids"][:, 1:]
        mask = batch.get("attention_mask",
                         jnp.ones_like(batch["input_ids"]))
        w = mask[:, 1:].astype(jnp.float32)
        h = self.encode(params, batch, rng, train=True)
        every = self.accuracy_every_n
        step = (extras.get("lm_step")
                if every != 1 and isinstance(extras, dict) else None)
        if every == 1 or self.cfg.loss_impl == "fused" or step is None:
            # fused gets the argmax free from the same vocab scan; step
            # is None for direct callers that never initialized the
            # counter (init() emits it only when the knob is set)
            loss, acc = self._lm_loss(params, h, targets, w)
            return loss, ({"token_accuracy": acc}, extras)
        # every-n cadence: one branch runs per step (lax.cond), so the
        # full-vocab argmax is genuinely skipped on non-multiple steps
        loss, acc = lax.cond(
            jnp.mod(step, float(every)) == 0,
            lambda: self._lm_loss(params, h, targets, w, accuracy=True),
            lambda: self._lm_loss(params, h, targets, w, accuracy=False))
        new_extras = dict(extras)
        new_extras["lm_step"] = step + 1.0
        return loss, ({"token_accuracy": acc}, new_extras)

    def eval_metrics(self, params, extras, batch) -> dict:
        targets = batch["input_ids"][:, 1:]
        mask = batch.get("attention_mask",
                         jnp.ones_like(batch["input_ids"]))
        w = mask[:, 1:].astype(jnp.float32)
        valid = batch.get("__valid__")
        if valid is not None:
            w = w * valid.astype(jnp.float32)[:, None]
        # eval rides the configured impl too: the final eval of a
        # chunked/fused run must not re-materialize the [B, S, vocab]
        # tensor the lever exists to avoid — and always reports
        # accuracy (the every-n knob is a per-train-step economy)
        h = self.encode(params, batch, train=False)
        loss, acc = self._lm_loss(params, h, targets, w)
        return {
            "loss": loss,
            # the classic LM headline number; exp of the masked mean xent
            "perplexity": jnp.exp(loss),
            "token_accuracy": acc,
        }

    # ------------------------------------------------------------------
    # autoregressive decoding (static-shape KV cache, one compiled scan)
    # ------------------------------------------------------------------
    def _prefill_full(self, params, ids, total_len: int, *, mask=None,
                      pos_ids=None):
        """Full causal forward over the (possibly padded) prompt,
        additionally returning per-layer K/V padded to ``total_len``
        slots. ``mask``/``pos_ids`` serve the ragged-prompt path: pad
        slots are attention-masked out and real tokens carry their own
        positions. Returns (hidden [B,S,hid] post-ln_f, caches
        {layer_i: {k, v}: [B,T,H,D]})."""
        c = self.cfg
        _, s = ids.shape
        if mask is None:
            mask = jnp.ones_like(ids)
        if pos_ids is None:
            pos_ids = jnp.arange(s, dtype=jnp.int32)[None]
        h, _ = self._embed(params, ids, pos_ids, rng=None, train=False)
        caches = {}
        pad = [(0, 0), (0, total_len - s), (0, 0), (0, 0)]
        for i in range(c.layers):
            h, (k, v) = self._layer(params[f"layer_{i}"], h, mask, None,
                                    train=False, use_dropout=False,
                                    return_kv=True)
            caches[f"layer_{i}"] = {"k": jnp.pad(k, pad),
                                    "v": jnp.pad(v, pad)}
        h = nn.layernorm(params["ln_f"], h)
        return h, caches

    def _prefill(self, params, ids, total_len: int, *, mask=None,
                 pos_ids=None):
        """:meth:`_prefill_full` sliced to the LAST slot's hidden state
        — the right-packed contract (every row's prompt ends at slot
        S0-1) the monolithic ``generate`` path runs on."""
        h, caches = self._prefill_full(params, ids, total_len, mask=mask,
                                       pos_ids=pos_ids)
        return h[:, -1], caches

    def _decode_step(self, params, caches, tok, pos, pad=None):
        """One-token forward against the cache. ``tok`` [B] int32,
        ``pos`` scalar (the CACHE SLOT tok sits at). ``pad`` [B] is the
        per-row left-pad count of a ragged prompt: row b's token at slot
        j holds position j - pad_b, and slots below pad_b are dead.
        Returns (logits [B,V], updated caches)."""
        c = self.cfg
        b = tok.shape[0]
        total = jax.tree_util.tree_leaves(caches)[0].shape[1]
        if pad is None:
            pad = jnp.zeros((b,), jnp.int32)
        h, _ = self._embed(params, tok[:, None], (pos - pad)[:, None],
                           rng=None, train=False)
        slots = jnp.arange(total, dtype=jnp.int32)
        kmask = (slots[None, :] <= pos) & (slots[None, :] >= pad[:, None])
        new_caches = {}
        for i in range(c.layers):
            lp = params[f"layer_{i}"]
            cache = caches[f"layer_{i}"]
            q, k, v = self._qkv(lp["attn"], nn.layernorm(lp["ln1"], h))
            ck = lax.dynamic_update_slice_in_dim(cache["k"],
                                                 k.astype(cache["k"].dtype),
                                                 pos, axis=1)
            cv = lax.dynamic_update_slice_in_dim(cache["v"],
                                                 v.astype(cache["v"].dtype),
                                                 pos, axis=1)
            new_caches[f"layer_{i}"] = {"k": ck, "v": cv}
            ctx = multi_head_attention(
                q, ck, cv, mask=kmask[:, None, None, :],
                impl="xla")     # 1-query attention: tiles never pay off
            a = nn.dense(lp["attn"]["o"], ctx.reshape(b, 1, c.hidden),
                         dtype=self.dtype)
            h = h + a.astype(h.dtype)
            f = self._ffn(lp, nn.layernorm(lp["ln2"], h))
            h = h + f.astype(h.dtype)
        h = nn.layernorm(params["ln_f"], h)
        return self.lm_logits(params, h)[:, 0], new_caches

    # ------------------------------------------------------------------
    # decode fast path: stacked layer axis + lax.scan + fused QKV
    # ------------------------------------------------------------------
    def stack_decode_params(self, params, *, weight_quant: str | None = None):
        """Restack the per-layer param dicts into ONE pytree with a
        leading layer axis, with the Q/K/V projections fused into a
        single [hid, 3*hid] kernel per layer. The decode layer loop then
        runs as ``lax.scan`` over this stack: one traced layer body
        instead of ``layers`` unrolled copies, and one fat QKV matmul
        per layer instead of three skinny ones — the kernel-count floor
        attack PROFILE_r05_decode motivates.

        ``weight_quant="int8"`` additionally stores the four matmul
        kernels as symmetric per-output-channel int8 (scale = f32 row
        max / 127), halving decode weight traffic for the stacked
        layers. LOSSY: greedy parity with the bf16 path is NOT
        guaranteed — it exists as the decode lever table's int8
        comparison row. Embeddings / LM head / layernorms stay in
        ``param_dtype``.

        Cost note: ``generate`` restacks INSIDE the compiled program,
        once per generation (``params`` is a runtime argument to the
        caller's jit, so XLA cannot constant-fold it) — one extra
        param read+write against the ``max_new`` weight re-reads of
        the decode loop, <2% of a 128-token generation's traffic and
        paid identically by every lever row except ``loop``. Exported
        artifacts bake params as constants, so there the restack
        folds away at trace time.
        """
        if weight_quant not in (None, "int8"):
            raise ValueError(f"weight_quant must be None or 'int8', got "
                             f"{weight_quant!r}")
        c = self.cfg
        lps = [params[f"layer_{i}"] for i in range(c.layers)]

        def stk(fn):
            return jnp.stack([fn(lp) for lp in lps])

        def dense_stack(fn):
            d = {"kernel": stk(lambda lp: fn(lp)["kernel"]),
                 "bias": stk(lambda lp: fn(lp)["bias"])}
            if weight_quant == "int8":
                w = d.pop("kernel").astype(jnp.float32)
                scale = jnp.maximum(
                    jnp.max(jnp.abs(w), axis=1, keepdims=True), 1e-8) / 127.0
                d["kernel_q"] = jnp.round(w / scale).astype(jnp.int8)
                d["scale"] = scale
            return d

        return {
            "ln1": {"scale": stk(lambda lp: lp["ln1"]["scale"]),
                    "bias": stk(lambda lp: lp["ln1"]["bias"])},
            "qkv": dense_stack(lambda lp: {
                "kernel": jnp.concatenate(
                    [lp["attn"][n]["kernel"] for n in ("q", "k", "v")],
                    axis=1),
                "bias": jnp.concatenate(
                    [lp["attn"][n]["bias"] for n in ("q", "k", "v")])}),
            "o": dense_stack(lambda lp: lp["attn"]["o"]),
            "ln2": {"scale": stk(lambda lp: lp["ln2"]["scale"]),
                    "bias": stk(lambda lp: lp["ln2"]["bias"])},
            "ffn_in": dense_stack(lambda lp: lp["ffn"]["in"]),
            "ffn_out": dense_stack(lambda lp: lp["ffn"]["out"]),
        }

    def _dequant(self, dp):
        """int8-stacked dense params -> plain {kernel, bias} (no-op for
        unquantized stacks). Runs INSIDE the layer scan body, so the
        int8 tensors are what crosses HBM per layer step."""
        if "kernel_q" not in dp:
            return dp
        w = (dp["kernel_q"].astype(jnp.float32) * dp["scale"])
        return {"kernel": w.astype(self.dtype), "bias": dp["bias"]}

    def _decode_step_stacked(self, params, stacked, caches, tok, pos,
                             pad=None, decode_attention: str | None = None):
        """One-token forward as ONE ``lax.scan`` over the stacked layer
        axis. Same contract as :meth:`_decode_step` (exact greedy
        parity is tier-1-tested), but the per-token program is the
        compact fast path: fused QKV, 2-D [B, hid] residual stream (no
        singleton seq axis to re-tile), and the cache-slab attention as
        either the single-query Pallas kernel or the XLA reference.

        ``caches``: ``{"k": [L, B, T, H, D], "v": [L, B, T, H, D]}`` —
        the per-layer slabs stacked along the scan axis.
        """
        from ..ops.pallas.decode_attention import (decode_attention as
                                                   decode_attn)
        c = self.cfg
        b = tok.shape[0]
        impl = decode_attention or self.decode_attention_impl
        if pad is None:
            pad = jnp.zeros((b,), jnp.int32)
        h, _ = self._embed(params, tok[:, None], (pos - pad)[:, None],
                           rng=None, train=False)
        h = h[:, 0]                                       # [B, hid]

        def body(h, xs):
            lp, ck, cv = xs
            qkv = nn.dense(self._dequant(lp["qkv"]),
                           nn.layernorm(lp["ln1"], h), dtype=self.dtype)
            q, k, v = [x.reshape(b, c.heads, self.head_dim)
                       for x in jnp.split(qkv, 3, axis=-1)]
            ck = lax.dynamic_update_slice(
                ck, k[:, None].astype(ck.dtype), (0, pos, 0, 0))
            cv = lax.dynamic_update_slice(
                cv, v[:, None].astype(cv.dtype), (0, pos, 0, 0))
            ctx = decode_attn(q, ck, cv, pos=pos, pad=pad, impl=impl)
            a = nn.dense(self._dequant(lp["o"]), ctx.reshape(b, c.hidden),
                         dtype=self.dtype)
            h = h + a.astype(h.dtype)
            f = nn.dense(self._dequant(lp["ffn_in"]),
                         nn.layernorm(lp["ln2"], h), dtype=self.dtype)
            f = jax.nn.gelu(f.astype(jnp.float32)).astype(self.dtype)
            f = nn.dense(self._dequant(lp["ffn_out"]), f, dtype=self.dtype)
            h = h + f.astype(h.dtype)
            return h, (ck, cv)

        h, (ks, vs) = lax.scan(body, h,
                               (stacked, caches["k"], caches["v"]))
        h = nn.layernorm(params["ln_f"], h)
        return (self.lm_logits(params, h[:, None])[:, 0],
                {"k": ks, "v": vs})

    def ragged_prefill(self, params, input_ids, prompt_mask,
                       total_len: int):
        """Ragged-prompt prefill: right-pack every row's real tokens
        against slot S0-1 (stable argsort — order preserving), build
        per-row positions/attention from the pad count, and run
        :meth:`_prefill` padded to ``total_len`` cache slots. Returns
        ``(last_hidden [B, hid], caches, pad [B])``. ONE body for
        :meth:`generate`'s ragged branch and the stepwise serving
        export (serving.export_generator ``stepwise=True``) — the
        continuous-batching engine's admission prefill is the exact
        computation the monolithic path runs."""
        b, s0 = input_ids.shape
        # normalize to 0/1 first: the docstring contract is "nonzero
        # = real token", and a 2 in the mask would otherwise corrupt
        # the pad count below (and disagree with the HTTP server's
        # `!= 0` validation)
        pm = (jnp.asarray(prompt_mask) != 0).astype(jnp.int32)
        # stable argsort keys pads (0) first, real tokens (1) after
        # IN ORDER: one gather right-packs every row
        order = jnp.argsort(pm, axis=1, stable=True)
        ids = jnp.take_along_axis(jnp.asarray(input_ids), order, axis=1)
        pad = (s0 - jnp.sum(pm, axis=1)).astype(jnp.int32)
        valid = jnp.arange(s0, dtype=jnp.int32)[None, :] >= pad[:, None]
        ids = jnp.where(valid, ids, 0)
        pos_ids = jnp.maximum(
            jnp.arange(s0, dtype=jnp.int32)[None, :] - pad[:, None], 0)
        last_h, caches = self._prefill(params, ids, total_len,
                                       mask=valid.astype(jnp.int32),
                                       pos_ids=pos_ids)
        return last_h, caches, pad

    def decode_step_batched(self, params, stacked, caches, tok, pos,
                            pad, alive=None,
                            decode_attention: str | None = None):
        """One-token forward with PER-ROW cache depths — the decode
        step of the continuous-batching serving engine, where slots
        were admitted at different times and therefore sit at
        different positions in their own sequences.

        Same fast-path body as :meth:`_decode_step_stacked` (one
        ``lax.scan`` over the stacked layer axis, fused QKV, 2-D
        residual stream) with two generalizations:

        - ``pos`` is [B] int32 (row b's token writes cache slot
          ``pos[b]`` and carries position id ``pos[b] - pad[b]``)
          instead of one shared scalar;
        - ``alive`` [B] (bool / 0-1) gates the cache write: a retired
          slot's slab keeps its old bytes (its lane still computes —
          wasted work the shared step accepts — but cannot mutate the
          pool; admission prefill overwrites the whole slab anyway).

        Rows are independent: row b's logits depend only on row b's
        token/pos/pad/cache, which is what makes the engine's shared
        step produce the same token stream per request as a
        single-request run (tier-1 tested). ``caches``:
        ``{"k": [L, B, T, H, D], "v": [L, B, T, H, D]}``.
        """
        from ..ops.pallas.decode_attention import (decode_attention as
                                                   decode_attn)
        c = self.cfg
        b = tok.shape[0]
        total = caches["k"].shape[2]
        impl = decode_attention or self.decode_attention_impl
        pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, total - 1)
        pad = jnp.asarray(pad, jnp.int32)
        if alive is None:
            alive = jnp.ones((b,), bool)
        alive = jnp.asarray(alive) != 0
        # dead rows may carry stale pos/pad; clamp the position id so
        # the wpe lookup stays in range (live rows are unaffected —
        # their pos - pad is a real position by construction)
        pos_ids = jnp.clip(pos - pad, 0, c.max_len - 1)
        h, _ = self._embed(params, tok[:, None], pos_ids[:, None],
                           rng=None, train=False)
        h = h[:, 0]                                       # [B, hid]
        rows = jnp.arange(b)

        def body(h, xs):
            lp, ck, cv = xs
            qkv = nn.dense(self._dequant(lp["qkv"]),
                           nn.layernorm(lp["ln1"], h), dtype=self.dtype)
            q, k, v = [x.reshape(b, c.heads, self.head_dim)
                       for x in jnp.split(qkv, 3, axis=-1)]
            # per-row scatter at each row's own depth; dead rows
            # rewrite their old bytes (no-op write keeps the pool
            # stable for retired slots)
            k_w = jnp.where(alive[:, None, None],
                            k.astype(ck.dtype), ck[rows, pos])
            v_w = jnp.where(alive[:, None, None],
                            v.astype(cv.dtype), cv[rows, pos])
            ck = ck.at[rows, pos].set(k_w)
            cv = cv.at[rows, pos].set(v_w)
            ctx = decode_attn(q, ck, cv, pos=pos, pad=pad, impl=impl)
            a = nn.dense(self._dequant(lp["o"]), ctx.reshape(b, c.hidden),
                         dtype=self.dtype)
            h = h + a.astype(h.dtype)
            f = nn.dense(self._dequant(lp["ffn_in"]),
                         nn.layernorm(lp["ln2"], h), dtype=self.dtype)
            f = jax.nn.gelu(f.astype(jnp.float32)).astype(self.dtype)
            f = nn.dense(self._dequant(lp["ffn_out"]), f, dtype=self.dtype)
            h = h + f.astype(h.dtype)
            return h, (ck, cv)

        h, (ks, vs) = lax.scan(body, h,
                               (stacked, caches["k"], caches["v"]))
        h = nn.layernorm(params["ln_f"], h)
        return (self.lm_logits(params, h[:, None])[:, 0],
                {"k": ks, "v": vs})

    # ------------------------------------------------------------------
    # block-paged serving path (round 10): the KV pool is shared
    # physical blocks + per-slot block tables. ONE layout, stated here
    # once: ``[L, N, Bs, H*D]``, a token's heads side by side; layer
    # i's block b is row ``i * N + b`` of the flat ``[L*N, Bs, H*D]``
    # view the decode kernel reads. Every writer scatters whole token
    # rows and the kernel carves its [Bs, g*D] blocks from it as it
    # lies, so the cache is written and read where it lies: no program
    # re-lays a pool (tests/test_tpu_compile.py holds the compiled
    # programs to that).
    # ------------------------------------------------------------------
    def paged_prefill(self, params, input_ids, prompt_mask, k_pool,
                      v_pool, table_row, *, k_scale=None, v_scale=None):
        """LEFT-ALIGNED prompt prefill writing WHOLE blocks through a
        block-table row — the paged serving engine's admission program.

        Unlike :meth:`ragged_prefill` (which right-packs so the
        monolithic loop can advance one shared scalar slot), the paged
        layout keeps token i at logical slot i: a shared token prefix
        then occupies the same leading blocks for every request
        regardless of total prompt length, which is what makes
        block-granularity prefix reuse possible at all (right-packing
        shifts the prefix by the per-request pad count). The engine's
        decode step has per-row ``pos`` anyway, so nothing needed the
        shared-scalar trick here.

        ``input_ids``/``prompt_mask``: [1, S0] (mask 1 = real token,
        left-aligned); ``k_pool``/``v_pool``: [L, N, Bs, H*D];
        ``table_row``: [ceil(S0 / Bs)] int32 physical block ids (the
        engine points unused trailing entries at the reserved null
        block 0 — whole-block writes land there and are never read).
        Returns ``(logits [1, V] of the last real token, k_pool',
        v_pool')`` with every prompt-capacity block of this row
        overwritten.

        ``k_scale``/``v_scale`` ([L, N, Bs] f32 parallel pools) switch
        on QUANTIZE-ON-WRITE for an int8 pool: each token row's H*D
        K/V values are stored symmetric int8 with their per-row scale
        (:func:`quantize_kv_rows` — deterministic in the bytes, so
        prefix-cache sharing mounts byte-identical blocks) and the
        return grows to ``(logits, k_pool', v_pool', k_scale',
        v_scale')``."""
        c = self.cfg
        _, s0 = input_ids.shape
        bs = k_pool.shape[2]
        nb_p = table_row.shape[0]
        total = nb_p * bs
        pm = (jnp.asarray(prompt_mask) != 0)
        ids = jnp.where(pm, jnp.asarray(input_ids), 0)
        h_full, caches = self._prefill_full(
            params, ids, total, mask=pm.astype(jnp.int32),
            pos_ids=jnp.arange(s0, dtype=jnp.int32)[None])
        p = jnp.sum(pm.astype(jnp.int32))
        last_h = jnp.take_along_axis(
            h_full, jnp.maximum(p - 1, 0)[None, None, None], axis=1)[:, 0]
        kv = self._stack_caches(caches)         # {"k"/"v": [L,1,T,H,D]}
        l = c.layers

        def scatter(pool, stacked):
            blocks = stacked[:, 0].reshape(l, nb_p, bs, c.hidden)
            return pool.at[:, table_row].set(blocks.astype(pool.dtype))

        logits = self.lm_logits(params, last_h[:, None])[:, 0]
        if k_scale is None:
            return logits, scatter(k_pool, kv["k"]), scatter(v_pool,
                                                             kv["v"])
        # int8 pool: quantize each token row before the block scatter;
        # the scale rows ride a parallel [L, N, Bs] pool through the
        # same table indices
        def scatter_q(pool, spool, stacked):
            q, s = quantize_kv_rows(                  # [L,T,H*D] / [L,T]
                stacked[:, 0].reshape(l, total, c.hidden))
            qb = q.reshape(l, nb_p, bs, c.hidden)
            sb = s.reshape(l, nb_p, bs)
            return (pool.at[:, table_row].set(qb),
                    spool.at[:, table_row].set(sb))
        kq, ks = scatter_q(k_pool, k_scale, kv["k"])
        vq, vs = scatter_q(v_pool, v_scale, kv["v"])
        return logits, kq, vq, ks, vs

    def paged_prefill_chunk(self, params, input_ids, chunk_mask, start,
                            k_pool, v_pool, table_row, chunk_blocks, *,
                            k_scale=None, v_scale=None):
        """ONE ``chunk_tokens``-wide slice of a left-aligned paged
        prefill — the SLO scheduler's bounded-stall admission program
        (round 18). A long prompt's monolithic prefill stalls every
        live decode slot for the whole forward; this program processes
        only the tokens at logical slots ``start .. start+C-1``,
        reading the PRIOR chunks' K/V back from the pool through
        ``table_row``, so the engine can interleave shared decode steps
        between chunks and bound the worst-case decode stall at one
        chunk's dispatch time.

        ``input_ids``/``chunk_mask``: [1, C] (mask 1 = real token,
        left-aligned — only the final chunk of a prompt is ragged);
        ``k_pool``/``v_pool``: [L, N, Bs, H*D];
        ``start``: scalar int32, the chunk's first logical slot (the
        engine keeps it block-aligned); ``table_row``: [NB_p] int32,
        the slot's WHOLE prompt-capacity block run (the attention
        gather's context window); ``chunk_blocks``: [C / Bs] int32,
        the physical blocks this chunk writes (entries past the
        prompt's allocated run point at the reserved null block 0,
        whose bytes are never read). Returns ``(logits [1, V] of the
        chunk's last real token, k_pool', v_pool')`` — the logits only
        matter on the FINAL chunk, where they are the request's first
        sample point, exactly like :meth:`paged_prefill`'s return.

        Parity contract: per-token math is row-independent (embedding,
        layernorm, dense) and the attention softmax over the gathered
        pool window differs from the monolithic prefill's only by
        exactly-zero masked terms, so with a float pool (storage dtype
        == compute dtype) the chunked byte stream — K/V block bytes
        AND the final-chunk logits — is bit-identical to one
        :meth:`paged_prefill` dispatch (tier-1 tested). An int8 pool
        re-reads prior chunks through the quantize/dequant pair the
        monolithic prefill never pays, so int8 composition rides the
        repo's token-agreement drift gate instead (DESIGN.md §15).

        ``k_scale``/``v_scale`` switch on quantize-on-write exactly as
        in :meth:`paged_prefill`; the return grows the same way."""
        c = self.cfg
        _, cw = input_ids.shape
        bs = k_pool.shape[2]
        nb_c = chunk_blocks.shape[0]
        nb_p = table_row.shape[0]
        total = nb_p * bs
        start = jnp.asarray(start, jnp.int32)
        cm = (jnp.asarray(chunk_mask) != 0)
        ids = jnp.where(cm, jnp.asarray(input_ids), 0)
        # global positions; masked lanes clamp so the wpe gather stays
        # in range (their rows are garbage nothing reads)
        pos_ids = jnp.clip(start + jnp.arange(cw, dtype=jnp.int32),
                           0, c.max_len - 1)[None]
        h, _ = self._embed(params, ids, pos_ids, rng=None, train=False)
        # key validity over the gathered context window: every slot
        # before this chunk holds a real prior-chunk token (chunks tile
        # block-aligned), slots inside the chunk follow its mask, and
        # slots at or past the chunk end were never written
        slots = jnp.arange(total, dtype=jnp.int32)
        in_chunk = (slots >= start) & (slots < start + cw)
        chunk_valid = jnp.take(
            cm[0], jnp.clip(slots - start, 0, cw - 1))
        kv_valid = (slots < start) | (in_chunk & chunk_valid)
        # causal: query lane j (global slot start + j) sees slot s
        # iff s <= start + j
        qpos = start + jnp.arange(cw, dtype=jnp.int32)
        mask4 = (kv_valid[None, :]
                 & (slots[None, :] <= qpos[:, None]))[None, None]
        quant = k_scale is not None

        def write(pool, i, fresh):
            # [1, C, H, D] fresh K/V -> layer i's whole blocks of flat
            # token rows (same scatter shape as paged_prefill, through
            # chunk_blocks), in place on the pool
            blocks = fresh[0].reshape(nb_c, bs, c.hidden)
            return pool.at[i, chunk_blocks].set(blocks.astype(pool.dtype))

        def write_q(pool, spool, i, fresh):
            q, s = quantize_kv_rows(                   # [C,H*D] / [C]
                fresh[0].reshape(cw, c.hidden))
            return (pool.at[i, chunk_blocks].set(
                        q.reshape(nb_c, bs, c.hidden)),
                    spool.at[i, chunk_blocks].set(s.reshape(nb_c, bs)))

        for i in range(c.layers):
            lp = params[f"layer_{i}"]
            q, k, v = self._qkv(lp["attn"], nn.layernorm(lp["ln1"], h))
            # write THIS chunk's K/V first (verify-style: the gather
            # below must already see lanes 0..j-1's keys), then gather
            # the whole context window back through the table
            if quant:
                k_pool, k_scale = write_q(k_pool, k_scale, i, k)
                v_pool, v_scale = write_q(v_pool, v_scale, i, v)
                ctx_k = (k_pool[i, table_row].astype(jnp.float32)
                         * k_scale[i, table_row][..., None])
                ctx_v = (v_pool[i, table_row].astype(jnp.float32)
                         * v_scale[i, table_row][..., None])
            else:
                k_pool = write(k_pool, i, k)
                v_pool = write(v_pool, i, v)
                ctx_k, ctx_v = k_pool[i, table_row], v_pool[i, table_row]
            ctx_k = ctx_k.reshape(1, total, c.heads, self.head_dim) \
                .astype(self.dtype)
            ctx_v = ctx_v.reshape(1, total, c.heads, self.head_dim) \
                .astype(self.dtype)
            ctx = multi_head_attention(q, ctx_k, ctx_v, mask=mask4,
                                       impl="xla")
            a = nn.dense(lp["attn"]["o"],
                         ctx.reshape(1, cw, c.hidden), dtype=self.dtype)
            h = h + a.astype(h.dtype)
            f = self._ffn(lp, nn.layernorm(lp["ln2"], h))
            h = h + f.astype(h.dtype)
        h = nn.layernorm(params["ln_f"], h)
        p_chunk = jnp.sum(cm.astype(jnp.int32))
        last_h = jnp.take_along_axis(
            h, jnp.maximum(p_chunk - 1, 0)[None, None, None],
            axis=1)[:, 0]
        logits = self.lm_logits(params, last_h[:, None])[:, 0]
        out = (logits, k_pool, v_pool)
        if quant:
            out += (k_scale, v_scale)
        return out

    def decode_step_batched_paged(self, params, stacked, pools,
                                  block_tables, tok, pos, pad,
                                  alive=None,
                                  decode_attention: str | None = None):
        """:meth:`decode_step_batched` with the cache read/written
        THROUGH per-slot block tables: row b's token writes physical
        block ``block_tables[b, pos_b // Bs]`` at offset ``pos_b % Bs``,
        and attention gathers K/V through the same table (both decode-
        attention impls). ``pools``: ``{"k"/"v": [L, N, Bs, H*D]}``;
        ``block_tables``: [B, NB] int32. The pools ride the layer
        scan's CARRY: layer i writes its B token rows at
        ``[i, block, offset]`` in place and attention reads the flat
        ``[L*N, Bs, H*D]`` view through ``block_tables + i * N``, so
        no layer is sliced out of the pool or stacked back into it
        (as scanned ``xs``/``ys`` the pools cost a second pool and a
        copy of every layer's slice a step). Rows stay independent — the
        engine guarantees a written block is uniquely owned (copy-on-
        write happens host-side before the step), and a dead row's
        table points at the null block, where its gated write rewrites
        old bytes.

        int8 KV cache: when ``pools`` additionally carries
        ``"k_scale"``/``"v_scale"`` ([L, N, Bs] f32), the K/V pools
        are int8 — the step QUANTIZES its new row on write
        (:func:`quantize_kv_rows`, same per-row symmetric scheme as
        :meth:`paged_prefill`, so forced-suffix bytes match what a
        cold prefill of the same tokens writes up to the drift-gate
        contract) and both decode-attention impls fuse the dequant
        into the gather (no dequantized pool tensor ever exists)."""
        from ..ops.pallas.decode_attention import paged_decode_attention
        c = self.cfg
        b = tok.shape[0]
        n_layers, n, bs, _ = pools["k"].shape
        nb = block_tables.shape[1]
        impl = decode_attention or self.decode_attention_impl
        pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, nb * bs - 1)
        pad = jnp.asarray(pad, jnp.int32)
        bt = jnp.asarray(block_tables, jnp.int32)
        if alive is None:
            alive = jnp.ones((b,), bool)
        alive = jnp.asarray(alive) != 0
        # named scopes: metadata only (the HLO's op_name), so a profile
        # or a dump of the served decode program reads by part
        pos_ids = jnp.clip(pos - pad, 0, c.max_len - 1)
        with jax.named_scope("embed"):
            h, _ = self._embed(params, tok[:, None], pos_ids[:, None],
                               rng=None, train=False)
            h = h[:, 0]                                   # [B, hid]
        rows = jnp.arange(b)
        pbid = bt[rows, pos // bs]                        # [B] physical
        off = pos % bs
        quant = "k_scale" in pools

        def body(carry, xs):
            h, cache = carry
            lp, i = xs
            with jax.named_scope("qkv"):
                qkv = nn.dense(self._dequant(lp["qkv"]),
                               nn.layernorm(lp["ln1"], h),
                               dtype=self.dtype)
                q, k, v = jnp.split(qkv, 3, axis=-1)       # [B, H*D]
            with jax.named_scope("cache_write"):
                fresh = {"k": k, "v": v}
                if quant:
                    # quantize-on-write: the new row's int8 bytes + scale
                    fresh["k"], fresh["k_scale"] = quantize_kv_rows(k)
                    fresh["v"], fresh["v_scale"] = quantize_kv_rows(v)
                # the gated write, in place on the carried pools: a dead
                # row rewrites the bytes its (null) target already holds
                # (the gate is [B, 1] over K/V rows, [B] over scales)
                cache = {
                    name: pool.at[i, pbid, off].set(jnp.where(
                        alive.reshape((b,) + (1,) * (pool.ndim - 3)),
                        fresh[name].astype(pool.dtype), pool[i, pbid, off]))
                    for name, pool in cache.items()}
            with jax.named_scope("attention"):
                # read where it lies: every layer's blocks in one flat
                # view, layer i's block b at row i * N + b
                view = {name: pool.reshape(n_layers * n, *pool.shape[2:])
                        for name, pool in cache.items()}
                ctx = paged_decode_attention(
                    q.reshape(b, c.heads, self.head_dim), view["k"],
                    view["v"], block_tables=bt + i * n, pos=pos, pad=pad,
                    k_scale=view.get("k_scale"),
                    v_scale=view.get("v_scale"), impl=impl)
            with jax.named_scope("projection"):
                a = nn.dense(self._dequant(lp["o"]),
                             ctx.reshape(b, c.hidden), dtype=self.dtype)
                h = h + a.astype(h.dtype)
            with jax.named_scope("mlp"):
                f = nn.dense(self._dequant(lp["ffn_in"]),
                             nn.layernorm(lp["ln2"], h), dtype=self.dtype)
                f = jax.nn.gelu(f.astype(jnp.float32)).astype(self.dtype)
                f = nn.dense(self._dequant(lp["ffn_out"]), f,
                             dtype=self.dtype)
                h = h + f.astype(h.dtype)
            return (h, cache), None

        (h, out_pools), _ = lax.scan(
            body, (h, dict(pools)),
            (stacked, jnp.arange(n_layers, dtype=jnp.int32)))
        with jax.named_scope("head"):
            h = nn.layernorm(params["ln_f"], h)
            logits = self.lm_logits(params, h[:, None])[:, 0]
        return logits, out_pools

    def decode_verify_batched_paged(self, params, stacked, pools,
                                    block_tables, tok, pos, pad,
                                    alive, n_tok,
                                    decode_attention: str | None = None):
        """K-token VERIFY step for speculative decoding: row b carries
        ``tok[b] = [anchor, draft_1, ..., draft_{K-1}]`` — the anchor is
        the token a normal decode step would dispatch (its KV is not in
        the pool yet), the drafts are the self-drafter's proposals.
        Lane j writes its K/V at logical slot ``pos[b] + j`` through the
        block table and its logits predict the token at ``pos[b]+j+1``,
        so the host can accept the longest draft prefix that matches the
        greedy argmax chain and rewind ``pos`` past the rest.

        Implemented as :meth:`decode_step_batched_paged` over ROW-
        EXPANDED inputs: lane (b, j) becomes an independent row at
        ``pos[b] + j`` sharing row b's block table. Within one layer the
        scan body writes every row's K/V into the pool BEFORE the
        attention gather, so lane j's window (``slots <= pos[b]+j``)
        already contains lanes 0..j-1's keys — exactly the state a
        sequential dispatch of the same tokens would have produced. The
        verify step therefore inherits the batched step's byte-parity
        contract (rows are computationally independent) AND its whole
        quantization surface: int8 stacked weights and the int8 paged
        pool (quantize-on-write + fused-dequant gathers) run unchanged.

        ``tok``: [B, K] int32; ``pos``/``pad``/``alive``: [B];
        ``n_tok``: [B] int32 in [1, K] — lanes >= ``n_tok[b]`` are
        write-gated like dead rows (they rewrite old bytes; their
        logits are computed but the host ignores them), which is how
        draftless/sampled slots ride the same dispatch at width 1.
        Distinct lanes of one row write distinct (block, offset) pairs
        (positions ``pos..pos+K-1`` are consecutive), so the expanded
        scatter has no intra-row write collision. Returns
        (``logits [B, K, V]``, updated pools)."""
        b, kk = tok.shape
        lanes = jnp.arange(kk, dtype=jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        n_tok = jnp.asarray(n_tok, jnp.int32)
        alive = (jnp.asarray(alive) != 0)
        pos_e = (pos[:, None] + lanes[None, :]).reshape(-1)
        pad_e = jnp.repeat(jnp.asarray(pad, jnp.int32), kk)
        alive_e = (alive[:, None]
                   & (lanes[None, :] < n_tok[:, None])).reshape(-1)
        bt_e = jnp.repeat(jnp.asarray(block_tables, jnp.int32), kk,
                          axis=0)
        logits, new = self.decode_step_batched_paged(
            params, stacked, pools, bt_e, tok.reshape(-1), pos_e,
            pad_e, alive_e, decode_attention=decode_attention)
        return logits.reshape(b, kk, -1), new

    def _stack_caches(self, caches):
        """Per-layer {layer_i: {k, v}} prefill caches -> the stacked
        {"k": [L, ...], "v": [L, ...]} slabs the scan step consumes."""
        c = self.cfg
        return {n: jnp.stack([caches[f"layer_{i}"][n]
                              for i in range(c.layers)])
                for n in ("k", "v")}

    def _filter_logits(self, logits, top_k: int, top_p: float):
        """Nucleus/top-k filtering of [B, V] (temperature-scaled)
        logits: everything outside the kept set drops to the shared
        NEG_INF fill (exp underflows to exactly 0 under categorical).
        top-p keeps the smallest prefix of the descending-probability
        order whose EXCLUSIVE cumulative mass is < top_p — the top token
        always survives.

        Tie behavior (deliberate, ``>=``-threshold semantics): only
        logits STRICTLY below the kth-largest / nucleus-threshold value
        are dropped, so every token exactly TIED with the boundary
        survives — top_k can keep more than k tokens and top-p more
        than the nucleus mass on exact ties. Ties at the boundary are
        measure-zero in f32 practice; when they do occur, keeping both
        is the symmetric choice (dropping would need an arbitrary
        vocab-order preference). Covered by the tied-logits unit tests
        in tests/test_gpt.py."""
        from ..ops.attention import NEG_INF
        if top_k:
            kth = lax.top_k(logits, top_k)[0][:, -1:]
            logits = jnp.where(logits < kth, NEG_INF, logits)
        if top_p > 0.0:
            sl = jnp.sort(logits, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(sl, axis=-1)
            keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
            thresh = jnp.min(jnp.where(keep, sl, jnp.inf), axis=-1,
                             keepdims=True)
            logits = jnp.where(logits < thresh, NEG_INF, logits)
        return logits

    def generate(self, params, input_ids, max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0, eos_id: int | None = None,
                 pad_id: int = 0, prompt_mask=None,
                 rng: jax.Array | None = None,
                 decode_impl: str = "stacked",
                 decode_attention: str | None = None,
                 tokens_per_dispatch: int = 1,
                 weight_quant: str | None = None):
        """Autoregressive generation — one compiled program (prefill +
        KV-cache decode loop), greedy (``temperature=0``) or sampled
        with optional ``top_k``/``top_p`` (nucleus) filtering.

        ``decode_impl`` picks the decode-step body: ``"stacked"`` (the
        default fast path — layer loop as ``lax.scan`` over restacked
        leading-axis params with fused QKV; greedy output is exactly
        the ``"loop"`` path's, tier-1-tested) or ``"loop"`` (the
        reference per-layer Python loop). ``decode_attention``
        overrides the model's ``decode_attention_impl`` for the stacked
        path (``"auto"``/``"pallas"``/``"xla"``).

        ``tokens_per_dispatch=K`` emits K tokens per decode-loop body
        (``lax.scan``'s unroll) so fixed per-iteration overhead
        amortizes across K token steps; output is exactly the K=1
        token stream. Requires ``eos_id=None`` (the early-stop
        ``while_loop`` has a dynamic trip count — nothing to unroll).

        ``weight_quant="int8"`` decodes against int8-quantized stacked
        layer weights (see :meth:`stack_decode_params`) — LOSSY, the
        lever-table comparison row, stacked path only.

        ``prompt_mask`` [B, S0] (1 = real token) admits RAGGED prompt
        batches: real tokens (left-aligned by convention; any layout is
        compacted order-preserving) are repacked against the RIGHT edge
        internally, so every row's prompt ends at slot S0-1 and the
        decode loop advances one shared scalar cache slot — no per-row
        scatter. Positions/attention account for the per-row pad count;
        each row must contain at least one real token.

        ``eos_id`` switches the fixed-trip ``lax.scan`` decode loop to a
        ``lax.while_loop`` that STOPS once every row has emitted EOS
        (the EOS itself is emitted; later slots hold ``pad_id``) — the
        early exit is device-side, still one dispatch.

        Returns [B, max_new_tokens] int32. Jit-compatible:
        ``jax.jit(partial(model.generate, max_new_tokens=K))``.
        """
        c = self.cfg
        b, s0 = input_ids.shape
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens must be >= 0, got "
                             f"{max_new_tokens}")
        if max_new_tokens == 0:
            return jnp.zeros((b, 0), jnp.int32)
        total = s0 + max_new_tokens
        if total > c.max_len:
            raise ValueError(
                f"prompt {s0} + max_new_tokens {max_new_tokens} exceeds "
                f"max_len {c.max_len}")
        if temperature > 0.0 and rng is None:
            raise ValueError("sampling (temperature > 0) needs rng")
        if (top_k or top_p) and temperature <= 0.0:
            raise ValueError("top_k/top_p shape the SAMPLING "
                             "distribution; greedy decoding "
                             "(temperature=0) would silently ignore "
                             "them — set temperature > 0")
        if not 0 <= top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {top_p}")
        if top_k < 0 or top_k > c.vocab_size:
            raise ValueError(f"top_k must be in [0, vocab_size="
                             f"{c.vocab_size}], got {top_k}")
        if decode_impl not in ("stacked", "loop"):
            raise ValueError(f"decode_impl must be 'stacked' or 'loop', "
                             f"got {decode_impl!r}")
        if tokens_per_dispatch < 1:
            raise ValueError(f"tokens_per_dispatch must be >= 1, got "
                             f"{tokens_per_dispatch}")
        if tokens_per_dispatch > 1 and eos_id is not None:
            raise ValueError(
                "tokens_per_dispatch > 1 needs eos_id=None: the EOS "
                "early-stop while_loop has a dynamic trip count, so "
                "there is no fixed K-step body to unroll")
        if weight_quant is not None and decode_impl != "stacked":
            raise ValueError("weight_quant needs decode_impl='stacked' "
                             "(only the stacked scan consumes the "
                             "quantized layer stack)")
        if decode_attention is not None and decode_impl != "stacked":
            raise ValueError(
                "decode_attention picks the stacked path's cache-slab "
                "attention; decode_impl='loop' always uses the XLA "
                "reference — silently ignoring the override would "
                "mislabel a benchmark")

        if prompt_mask is not None:
            if tuple(prompt_mask.shape) != (b, s0):
                raise ValueError(
                    f"prompt_mask shape {tuple(prompt_mask.shape)} != "
                    f"input_ids shape {(b, s0)}")
            last_h, caches, pad = self.ragged_prefill(
                params, input_ids, prompt_mask, total)
        else:
            pad = jnp.zeros((b,), jnp.int32)
            last_h, caches = self._prefill(params, input_ids, total)
        first_logits = self.lm_logits(params, last_h[:, None])[:, 0]

        if decode_impl == "stacked":
            stacked = self.stack_decode_params(params,
                                               weight_quant=weight_quant)
            caches = self._stack_caches(caches)

            def step(caches, tok, pos):
                return self._decode_step_stacked(
                    params, stacked, caches, tok, pos, pad,
                    decode_attention=decode_attention)
        else:
            def step(caches, tok, pos):
                return self._decode_step(params, caches, tok, pos, pad)

        def pick(logits, step_rng):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            scaled = self._filter_logits(logits / temperature, top_k,
                                         top_p)
            return jax.random.categorical(
                step_rng, scaled, axis=-1).astype(jnp.int32)

        def step_rng(step):
            return (jax.random.fold_in(rng, step)
                    if rng is not None else None)

        tok0 = pick(first_logits, step_rng(0))

        if eos_id is None:
            def body(carry, i):
                caches, tok, pos = carry
                logits, caches = step(caches, tok, pos)
                nxt = pick(logits, step_rng(i + 1))
                return (caches, nxt, pos + 1), tok

            # tokens_per_dispatch=K unrolls K token steps into each
            # loop body: ~1/K the loop-bookkeeping overhead per token,
            # and XLA schedules across the K steps' kernels
            (_, last_tok, _), toks = lax.scan(
                body, (caches, tok0, jnp.int32(s0)),
                jnp.arange(max_new_tokens - 1, dtype=jnp.int32),
                unroll=max(1, min(tokens_per_dispatch,
                                  max_new_tokens - 1)))
            # toks carries tokens 0..max_new-2 (each body emits its
            # INPUT token); the final pick is appended explicitly
            return jnp.concatenate([toks.transpose(1, 0),
                                    last_tok[:, None]], axis=1)

        # EOS early-stop: while_loop emits into a preallocated buffer
        # and exits as soon as every row is done — a batch whose rows
        # all finish by step k pays k+1 decode steps, not max_new
        out0 = jnp.full((b, max_new_tokens), pad_id, jnp.int32)

        def cond(carry):
            _, _, _, done, _, t = carry
            return (t < max_new_tokens) & jnp.logical_not(jnp.all(done))

        def wbody(carry):
            caches, tok, pos, done, out, t = carry
            emit = jnp.where(done, pad_id, tok)
            out = lax.dynamic_update_slice_in_dim(out, emit[:, None], t,
                                                  axis=1)
            done = done | (tok == eos_id)

            # the decode step computes the NEXT token — skip it when no
            # next slot will be emitted (last iteration, or every row
            # just finished), matching the scan path's
            # one-decode-per-emitted-token cost
            def dec(caches, tok, pos):
                logits, caches = step(caches, tok, pos)
                return pick(logits, step_rng(t + 1)), caches

            nxt, caches = lax.cond(
                (t + 1 < max_new_tokens) & jnp.logical_not(jnp.all(done)),
                dec, lambda caches, tok, pos: (tok, caches),
                caches, tok, pos)
            return (caches, nxt, pos + 1, done, out, t + 1)

        carry = (caches, tok0, jnp.int32(s0),
                 jnp.zeros((b,), bool), out0, jnp.int32(0))
        _, _, _, _, out, _ = lax.while_loop(cond, wbody, carry)
        return out

    # ------------------------------------------------------------------
    def sharding_rules(self, mesh_shape) -> ShardingRules:
        """Megatron TP, same shapes as Bert; vocab-sharded tied head."""
        M = AxisNames.MODEL
        fsdp = getattr(mesh_shape, "fsdp", 1) if mesh_shape else 1
        tp = getattr(mesh_shape, "model", 1) if mesh_shape else 1
        if tp <= 1:
            return ShardingRules(fsdp_axis_size=fsdp)
        return ShardingRules(rules=[
            (r"attn/(q|k|v)/kernel", P(None, M)),
            (r"attn/(q|k|v)/bias", P(M)),
            (r"attn/o/kernel", P(M, None)),
            (r"ffn/in/kernel", P(None, M)),
            (r"ffn/in/bias", P(M)),
            (r"ffn/out/kernel", P(M, None)),
            (r"\bwte/table", P(M, None)),       # vocab-sharded tied head
        ], fsdp_axis_size=fsdp)

    def dummy_batch(self, batch_size: int):
        c = self.cfg
        rs = np.random.RandomState(0)
        s = min(128, c.max_len)
        return {
            "input_ids": rs.randint(0, c.vocab_size, (batch_size, s),
                                    dtype=np.int32),
            "attention_mask": np.ones((batch_size, s), np.int32),
        }


def _make(config: TrainConfig, cfg: GPTConfig, *,
          config_vocab: bool = True) -> GPT:
    if config_vocab:
        cfg.vocab_size = config.data.vocab_size
    cfg.max_len = max(cfg.max_len, config.data.seq_len)
    # loud config-time validation of the LM-loss lever surface (impl /
    # chunk / vocab block / accuracy cadence), before any trace
    ls = lm_loss_settings(config)
    cfg.loss_impl = ls["impl"]
    cfg.loss_chunk = ls["chunk"]
    cfg.loss_vocab_block = ls["vocab_block"]
    return GPT(cfg, dtype=resolve_dtype(config.dtype),
               attention_impl=config.attention_impl,
               param_dtype=resolve_dtype(config.param_dtype),
               remat=config.remat,
               attention_kwargs=flash_attention_kwargs(config),
               accuracy_every_n=ls["accuracy_every_n"])


@register_model("gpt")
def _make_gpt(config: TrainConfig) -> GPT:
    return _make(config, GPTConfig.small())


@register_model("gpt_tiny")
def _make_gpt_tiny(config: TrainConfig) -> GPT:
    return _make(config, GPTConfig.tiny(), config_vocab=False)

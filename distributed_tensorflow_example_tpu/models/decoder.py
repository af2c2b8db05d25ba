"""A served decoder built from a block description.

``models/gpt.py`` is one decoder written out by hand: LayerNorm, learned
positions, a GELU FFN, one head count, a tied head. The decoders people
deploy differ from it in a handful of choices a layer at a time, so this
module takes those choices as data (:class:`DecoderBlockConfig`: widths,
query and KV head counts, q/k head norms, rotary base, the router's width
and the experts held here, the generation block length) and builds from
them the two forwards the paged engine drives
(``serving_batch.GenerationEngine``):

- :meth:`BlockDecoder.paged_prefill`: one prompt under the block-causal
  mask, its K/V written in whole pool blocks through a block-table row;
- :meth:`BlockDecoder.block_step`: one batched step of ``B`` lanes a slot
  (``B`` = ``block_length``) for generation by diffusion over blocks: the
  lanes' K/V go into the pool through the block table BEFORE the attention
  gather, every lane attends to the logical slots up to its block's last,
  and each lane comes back as its argmax id and that id's float32 softmax
  probability. Never logits: at 64 slots x 4 lanes x 151,936 float32 they
  would be 155 MB a step.

What every block built here shares is not a field: RMSNorm, rotate-half
rotary positions over the whole head, routed gated-SiLU experts, an
untied head, no bias, no dropout (the benchmark's set-up writes a
``dropout`` onto every model's config; nothing here reads it). A second
norm, position scheme or FFN kind becomes a field when a forward
implements it.

Registered as ``sdar_moe`` (SDAR-30B-A3B-Chat's block: RMSNorm, rotary
positions, grouped-query attention with per-head q/k RMSNorm, 128 routed
experts top-8, no bias, untied head) and ``sdar_moe_tiny`` (the same
block at test widths). Nothing here trains: ``loss`` says so.

Equations (per layer, pre-norm, no bias anywhere)::

    h = x + W_o . Attn(rope(rms_d(W_q n)), rope(rms_d(W_k n)), W_v n),
        n = rms(x)
    y = h + sum_{e in top-8(p)} p_e / sum(p_top) . W_down,e (silu(W_gate,e m)
        * W_up,e m),   m = rms(h),   p = softmax(W_r m)   (float32)

Parameters are stored in ``param_dtype`` (the deployment's bfloat16),
matmul operands are ``dtype`` with float32 accumulation; the residual
stream, norms, rotary angles, router and confidences are float32.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..config import TrainConfig
from ..ops.moe import moe_dropless
from .base import DefaultRulesMixin, register_model, resolve_dtype


@dataclasses.dataclass
class DecoderBlockConfig:
    """What a decoder's layers are made of: the choices the forwards
    below take as data (the module's docstring lists what they fix). A
    value they do not implement is refused at construction."""
    vocab_size: int = 151936
    hidden: int = 2048
    layers: int = 48
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    norm_eps: float = 1e-6
    rope_theta: float = 1e6
    qk_norm: bool = True            # per-head RMSNorm on q and k
    experts: int = 128              # the router's width, as published
    experts_per_token: int = 8
    expert_width: int = 768
    #: the share of the experts this chip holds: ``experts_held`` experts
    #: from ``first_expert`` on (0 = all of them)
    experts_held: int = 0
    first_expert: int = 0
    #: generation by diffusion over blocks of this many positions;
    #: 1 would be the causal mask
    block_length: int = 4
    mask_id: int = 151669
    #: the engine's defaults for this model: denoising forwards a block
    #: may take (at least ``block_length / denoising_steps`` positions
    #: are committed by each) and the confidence above which a position
    #: is committed at once (``low_confidence_dynamic``)
    denoising_steps: int = 4
    confidence_threshold: float = 0.9
    max_len: int = 32768

    @classmethod
    def sdar_30b_a3b(cls) -> "DecoderBlockConfig":
        return cls()

    @classmethod
    def tiny(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=512, hidden=64, layers=2, heads=4, kv_heads=2,
                   head_dim=16, experts=8, experts_per_token=2,
                   expert_width=32, mask_id=511, max_len=256)

    @property
    def held(self) -> int:
        return self.experts_held or self.experts


def _rms(x, scale, eps: float):
    """RMSNorm over the last axis in float32 (x and the result)."""
    x = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * inv * scale.astype(jnp.float32)


def _rope(x, pos, theta: float):
    """Rotary positions, rotate-half over the whole head: ``x`` [T, H, D]
    float32, ``pos`` [T] int32."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


class BlockDecoder(DefaultRulesMixin):
    name = "sdar_moe"

    def __init__(self, cfg: DecoderBlockConfig, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16):
        if cfg.heads % cfg.kv_heads:
            raise ValueError(f"{cfg.heads} query heads do not divide over "
                             f"{cfg.kv_heads} KV heads")
        b = cfg.block_length
        if b < 1 or b & (b - 1):
            raise ValueError(f"block_length must be a power of two, got {b}")
        if cfg.first_expert + cfg.held > cfg.experts:
            raise ValueError(
                f"experts {cfg.first_expert}..{cfg.first_expert + cfg.held}"
                f" are not among the {cfg.experts} the router knows")
        self.cfg = cfg
        self.dtype = dtype
        self.param_dtype = param_dtype
        #: operands of the router's product (see ops/moe.moe_dropless)
        self.router_dtype = jnp.float32

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array):
        """Seeded parameters in ``param_dtype`` (tests; a served
        checkpoint or the benchmark's seeded leaves replace them)."""
        c = self.cfg
        keys = iter(jax.random.split(rng, 2 + 8 * c.layers))
        qd, kd = c.heads * c.head_dim, c.kv_heads * c.head_dim

        def glorot(*shape):
            std = math.sqrt(2.0 / (shape[-2] + shape[-1]))
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(self.param_dtype)

        def ones(n):
            return jnp.ones((n,), self.param_dtype)

        params = {
            "embed": (jax.random.normal(next(keys),
                                        (c.vocab_size, c.hidden),
                                        jnp.float32) * 0.02
                      ).astype(self.param_dtype),
            "layers": {},
            "norm_f": ones(c.hidden),
            "head": glorot(c.hidden, c.vocab_size),
        }
        for i in range(c.layers):
            params["layers"][str(i)] = {
                "attn_norm": ones(c.hidden),
                "attn": {"wq": glorot(c.hidden, qd),
                         "wk": glorot(c.hidden, kd),
                         "wv": glorot(c.hidden, kd),
                         "wo": glorot(qd, c.hidden),
                         "q_norm": ones(c.head_dim),
                         "k_norm": ones(c.head_dim)},
                "ffn_norm": ones(c.hidden),
                "moe": {"router": glorot(c.hidden, c.experts),
                        "gate": glorot(c.held, c.hidden, c.expert_width),
                        "up": glorot(c.held, c.hidden, c.expert_width),
                        "down": glorot(c.held, c.expert_width, c.hidden)},
            }
        return params

    # ---- the trainer's protocol: this model is served, not trained ----
    def _serving_only(self, *_, **__):
        raise NotImplementedError(
            f"{self.name} is a served decoder (paged_prefill / block_step "
            "through serving.export_generator); it has no training path")

    apply = loss = eval_metrics = dummy_batch = _serving_only

    # ------------------------------------------------------------------
    def _mm(self, x, w):
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _qkv(self, lp, h, pos):
        """``h`` [T, hidden] float32, ``pos`` [T] -> q [T, NH, D],
        k, v [T, KVH, D] in ``dtype``, q and k normed and rotated."""
        c = self.cfg
        t = h.shape[0]
        ap = lp["attn"]
        n = _rms(h, lp["attn_norm"], c.norm_eps)
        q = self._mm(n, ap["wq"]).reshape(t, c.heads, c.head_dim)
        k = self._mm(n, ap["wk"]).reshape(t, c.kv_heads, c.head_dim)
        v = self._mm(n, ap["wv"]).reshape(t, c.kv_heads, c.head_dim)
        if c.qk_norm:
            q = _rms(q, ap["q_norm"], c.norm_eps)
            k = _rms(k, ap["k_norm"], c.norm_eps)
        q = _rope(q, pos, c.rope_theta)
        k = _rope(k, pos, c.rope_theta)
        return q.astype(self.dtype), k.astype(self.dtype), v.astype(
            self.dtype)

    def _ffn(self, lp, h):
        c = self.cfg
        m = _rms(h, lp["ffn_norm"], c.norm_eps)
        mp = lp["moe"]
        y, rows = moe_dropless(
            m, mp["router"], mp, top_k=c.experts_per_token,
            first_expert=c.first_expert, dtype=self.dtype,
            router_dtype=self.router_dtype)
        return h + y, rows

    def _sample(self, params, h):
        """Greedy candidates: per row the argmax id and its float32
        softmax probability (the confidence)."""
        c = self.cfg
        with jax.named_scope("sample"):
            logits = self._mm(_rms(h, params["norm_f"], c.norm_eps),
                              params["head"])               # [T, V] f32
            top = jnp.max(logits, axis=-1)
            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            return ids, jnp.exp(top - lse)

    # ------------------------------------------------------------------
    def paged_prefill(self, params, input_ids, prompt_mask, k_pool, v_pool,
                      table_row, *, attention: str = "flash"):
        """One prompt under the block-causal mask, its K/V written in
        whole pool blocks through ``table_row``.

        ``input_ids``/``prompt_mask`` [1, S0] (left-aligned; the mask is
        taken for the engine's sake and not needed: a position sees only
        its own block and earlier ones, so what lies past the prompt
        never reaches a whole block of it, and the engine's first block
        step overwrites the K/V of the block the prompt ends in);
        ``k_pool``/``v_pool`` [L, N, Bs, KVH * D]; ``table_row``
        [ceil(S0 / Bs)] physical block ids (unused entries name the null
        block 0). Returns the two pools. ``attention``: ``"flash"``
        (``flash_fwd`` with ``causal_block``) or ``"xla"``."""
        from ..ops.pallas.flash_attention import (
            flash_attention, xla_block_causal_attention)
        del prompt_mask
        c = self.cfg
        s0 = input_ids.shape[1]
        bs = k_pool.shape[2]
        nb_p = table_row.shape[0]
        group = c.heads // c.kv_heads
        pos = jnp.arange(s0, dtype=jnp.int32)
        h = params["embed"][input_ids[0]].astype(jnp.float32)

        def blocks(x):                      # [S0, KVH, D] -> pool blocks
            x = jnp.pad(x.reshape(s0, -1), ((0, nb_p * bs - s0), (0, 0)))
            return x.reshape(nb_p, bs, c.kv_heads * c.head_dim)

        for i in range(c.layers):
            lp = params["layers"][str(i)]
            with jax.named_scope("attention"):
                q, k, v = self._qkv(lp, h, pos)
                k_pool = k_pool.at[i, table_row].set(
                    blocks(k).astype(k_pool.dtype))
                v_pool = v_pool.at[i, table_row].set(
                    blocks(v).astype(v_pool.dtype))
                kr = jnp.repeat(k, group, axis=1)[None]
                vr = jnp.repeat(v, group, axis=1)[None]
                ctx = (flash_attention(q[None], kr, vr, causal=True,
                                       causal_block=c.block_length)
                       if attention == "flash" else
                       xla_block_causal_attention(q[None], kr, vr,
                                                  c.block_length))[0]
                h = h + self._mm(ctx.reshape(s0, -1), lp["attn"]["wo"])
            h, _ = self._ffn(lp, h)
        return k_pool, v_pool

    def block_step(self, params, k_pool, v_pool, block_tables, tok, pos,
                   alive, commit, *, attention: str = "auto"):
        """One batched block step: ``B`` lanes a slot.

        ``tok`` [slots, B] token ids (the mask id where masked), ``pos``
        [slots] the block's first logical slot (a multiple of B),
        ``block_tables`` [slots, NB], ``alive``/``commit`` [slots].
        Lane j of slot b writes its K/V at logical slot ``pos[b] + j``
        through the block table before the attention gather, and attends
        to the slots ``<= pos[b] + B - 1``. A denoising row and a commit
        row are the same computation: a commit row carries the block's
        final tokens, so the K/V it leaves is what later blocks read,
        while a denoising row's K/V lands in the same slots and is
        overwritten by its block's commit. A dead row's table names the
        null block, where its writes land unread.

        Returns ``ids`` [slots, B] int32 (each lane's argmax),
        ``conf`` [slots, B] float32 (its softmax probability; 0 on commit
        and dead rows, which unmask nothing), the pools, and two routing numbers:
        ``expert_rows`` (held experts that received a row, summed over
        layers) and ``max_expert_load`` (the fullest expert's rows over
        the mean a held expert would get)."""
        from ..ops.pallas.decode_attention import paged_block_attention
        c = self.cfg
        s, b = tok.shape
        n, bs = k_pool.shape[1], k_pool.shape[2]
        nb = block_tables.shape[1]
        group = c.heads // c.kv_heads
        bt = jnp.asarray(block_tables, jnp.int32)
        pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, nb * bs - b)
        lanes = jnp.arange(b, dtype=jnp.int32)
        pos_t = (pos[:, None] + lanes[None, :]).reshape(-1)     # [T]
        pbid = bt[jnp.arange(s), pos // bs][:, None]            # [S, 1]
        off = (pos % bs)[:, None] + lanes[None, :]              # [S, B]
        h = params["embed"][tok.reshape(-1)].astype(jnp.float32)
        flat = (c.layers * n, bs, c.kv_heads * c.head_dim)
        expert_rows = jnp.zeros((), jnp.int32)
        fullest = jnp.zeros((), jnp.int32)
        for i in range(c.layers):
            lp = params["layers"][str(i)]
            with jax.named_scope("attention"):
                q, k, v = self._qkv(lp, h, pos_t)
                k_pool = k_pool.at[i, pbid, off].set(
                    k.reshape(s, b, -1).astype(k_pool.dtype))
                v_pool = v_pool.at[i, pbid, off].set(
                    v.reshape(s, b, -1).astype(v_pool.dtype))
                # [T, NH, D] -> [S, KVH, group x B rows, D]; the pool is
                # read in place: layer i's blocks are i * N further on
                q = q.reshape(s, b, c.kv_heads, group, c.head_dim
                              ).transpose(0, 2, 3, 1, 4).reshape(
                    s, c.kv_heads, group * b, c.head_dim)
                ctx = paged_block_attention(
                    q, k_pool.reshape(flat), v_pool.reshape(flat),
                    block_tables=bt + i * n, last=pos + b - 1,
                    impl=attention)
                ctx = ctx.reshape(s, c.kv_heads, group, b, c.head_dim
                                  ).transpose(0, 3, 1, 2, 4).reshape(
                    s * b, c.heads * c.head_dim)
                h = h + self._mm(ctx, lp["attn"]["wo"])
            h, rows = self._ffn(lp, h)
            expert_rows += jnp.sum(rows > 0).astype(jnp.int32)
            fullest = jnp.maximum(fullest, jnp.max(rows))
        ids, conf = self._sample(params, h)
        unmasks = (jnp.asarray(alive) != 0) & (jnp.asarray(commit) == 0)
        conf = jnp.where(unmasks[:, None], conf.reshape(s, b), 0.0)
        mean_load = s * b * c.experts_per_token / c.experts
        return {"ids": ids.reshape(s, b), "conf": conf,
                "cache_k": k_pool, "cache_v": v_pool,
                "expert_rows": expert_rows,
                "max_expert_load": fullest.astype(jnp.float32) / mean_load}


def _make(config: TrainConfig, cfg: DecoderBlockConfig) -> BlockDecoder:
    if config.num_layers:
        cfg.layers = config.num_layers
    return BlockDecoder(cfg, dtype=resolve_dtype(config.dtype),
                        param_dtype=resolve_dtype(config.param_dtype))


@register_model("sdar_moe")
def _make_sdar_moe(config: TrainConfig) -> BlockDecoder:
    return _make(config, DecoderBlockConfig.sdar_30b_a3b())


@register_model("sdar_moe_tiny")
def _make_sdar_moe_tiny(config: TrainConfig) -> BlockDecoder:
    return _make(config, DecoderBlockConfig.tiny())

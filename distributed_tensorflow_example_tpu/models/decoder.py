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

``block_length = 1`` is plain causal generation, one token a step, and
the description then names a KIND a layer (``linear_attn``): the mixer
is Kimi Delta Attention (``ops/kda.py``: a float32 recurrent state a
request, no cache that grows) except on ``full_attn_layers``, where it
is multi-head latent attention without positions (``ops/mla.py``: one
latent row a token in a paged pool); the FFN of the first
``dense_layers`` is a dense gated SiLU, of the others sigmoid-routed
experts (a selection bias, renormalised top-k times ``routed_scale``)
plus ``shared_experts`` every token passes through; the embedding and
the head may hold a slice of the vocabulary (``vocab_held`` ids from
``first_vocab``). Its two forwards are :meth:`BlockDecoder.prefill_chunk`
(a fixed width of prompt tokens from the state carried in) and
:meth:`BlockDecoder.decode_step` (one token a slot), both returning
greedy ids, never logits. Registered as ``kimi_linear``
(Kimi-Linear-48B-A3B-Instruct's layers) and ``kimi_linear_tiny``; the
equations are in ``benchmark/reference/kimi-linear-48b-a3b.py``.

``layer_types`` names the kind a layer outright (one token a step too):
``full_attention`` layers mix by MLA over a SELECTED set of the earlier
rows (``mla_sparse``: a query low-rank pair, rotary positions on ``q_pe``
and on the shared ``k_pe`` before the row is cached, an indexer whose
keys lie in a second paged pool behind the same block tables,
``ops/dsa.py``), ``sliding_attention`` layers by MLA of another geometry
(``swa_*``: its own head count, ranks, head sizes and rotary base) over
the last ``window`` rows, kept in a ring a slot (``mla_window``); both
gate each head's output by ``sigmoid(W_g n)`` and rescale the normed
latents by ``sqrt(hidden / rank)``. The same two forwards, the same FFNs.
Registered as ``dots3_note`` (dots3-note-prev's layers) and
``dots3_note_tiny``; the equations are in
``benchmark/reference/dots3-note-prev.py``.

A description that names ``layer_types`` and no indexer (``index_topk``
0) mixes by GROUPED-QUERY attention in both kinds (``ops/gqa.py``):
``full_attention`` layers (``gqa_full``: ``heads`` query heads over
``kv_heads`` KV heads, rotary positions on the leading ``rotary_dim``
values of a head at YaRN's frequencies, ``rope_scaling``) keep every
row's K and V in a paged pool, ``sliding_attention`` layers
(``gqa_window``: ``swa_heads`` query heads over the same KV heads, plain
rotary positions at ``swa_rope_theta``) keep the last ``window`` rows in
a ring a slot; both gate each head's output (``head_gate``). Registered
as ``laguna`` (Laguna-S-2.1's layers) and ``laguna_tiny``; the equations
are in ``benchmark/reference/laguna-s-2.1.py``.

A description with a latent (``kv_lora_rank``), one token a step, NO
``linear_attn`` and NO ``layer_types`` mixes by DENSE latent attention
in every layer (``mla_dense``): dots3's query low-rank pair and rotary
positions on ``q_pe`` and on the shared ``k_pe`` before the row is
cached (at YaRN's frequencies, and the softmax scale times ``mscale^2``,
where ``rope_scaling`` / ``rope_mscale_all_dim`` say so), every earlier
row attended to: EXPANDED in a chunk (``ops/mla.mla_chunk_attention``:
K and V of a head made from a tile of latent rows) and ABSORBED in a
step (Kimi's ``mla_decode_attention``). Its whole per-request state is
the paged latent pool. Its sigmoid router may choose by groups
(``expert_groups``, ``top_expert_groups``). Registered as ``axk1``
(A.X-K1's layers) and ``axk1_tiny``; the equations are in
``benchmark/reference/a.x-k1.py``.

How a layer's kind follows from the description
(:meth:`DecoderBlockConfig.mixer`): ``layer_types`` names it with an
indexer over a latent (``mla_sparse`` / ``mla_window``) or without
(``gqa_full`` / ``gqa_window``); ``linear_attn`` makes it ``kda``, or
``mla`` on ``full_attn_layers``; a latent with neither is ``mla_dense``;
none of these is the block-diffusion ``gqa``.

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
from ..ops import dsa as dsa_ops
from ..ops import gqa as gqa_ops
from ..ops import kda as kda_ops
from ..ops import mla as mla_ops
from ..ops.moe import moe_dropless, pair_bound
from ..ops.pallas.decode_attention import _log_schedule
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
    # ---- a kind a layer (block_length = 1; see the module docstring) ----
    #: KDA mixers, MLA on ``full_attn_layers`` (numbered from 1, as
    #: published; entries past ``layers`` name layers held elsewhere)
    linear_attn: bool = False
    full_attn_layers: tuple = ()
    conv_kernel: int = 4            # KDA's short convolution over time
    gate_rank: int = 128            # rank of KDA's decay and gate pairs
    kv_lora_rank: int = 512         # MLA: the latent's normed values
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64           # (Kimi's are never rotated:
    #                                 mla_use_nope; see mla_rope)
    v_head_dim: int = 128
    dense_layers: int = 0           # leading layers with a dense FFN
    dense_width: int = 0
    shared_experts: int = 0
    router_scores: str = "softmax"  # or "sigmoid" (with a selection bias)
    routed_scale: float = 1.0
    #: the slice of the vocabulary this chip embeds and scores:
    #: ``vocab_held`` ids from ``first_vocab`` on (0 = all of it)
    vocab_held: int = 0
    first_vocab: int = 0
    # ---- a kind a layer by name (block_length = 1) ----
    #: ``full_attention`` / ``sliding_attention`` a layer, as published
    #: (entries past ``layers`` name layers held elsewhere); () = none
    layer_types: tuple = ()
    mla_rope: bool = False          # rotary on q_pe and on the cached k_pe
    q_lora_rank: int = 0            # 0: one query matrix
    lora_rescale: bool = False      # normed latents x sqrt(hidden / rank)
    head_gate: bool = False         # sigmoid(W_g n), one value a head
    index_heads: int = 0            # the indexer (full_attention layers)
    index_head_dim: int = 0
    index_topk: int = 0
    window: int = 0                 # rows a sliding layer sees, its own one
    swa_heads: int = 0              # the sliding layers' own geometry
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_dim: int = 0
    swa_qk_rope_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    # ---- grouped-query layers by name (layer_types without an indexer) --
    #: values of a full layer's head that are rotated (0 = all of them)
    rotary_dim: int = 0
    #: YaRN in the full layers: (factor, original context, beta_fast,
    #: beta_slow, attention factor); () = plain rotary at ``rope_theta``
    rope_scaling: tuple = ()
    # ---- dense latent attention in every layer (a latent, one token a
    # step, neither linear_attn nor layer_types) ----
    #: YaRN's ``mscale_all_dim`` of a latent layer: the softmax scale
    #: times ``(0.1 m ln factor + 1)^2``; 0 = the plain scale
    rope_mscale_all_dim: float = 0.0
    #: the sigmoid router chooses among the ``top_expert_groups`` best of
    #: ``expert_groups`` groups of experts (1 / 1: over all of them)
    expert_groups: int = 1
    top_expert_groups: int = 1

    @classmethod
    def a_x_k1(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=163840, hidden=7168, layers=61, heads=64,
                   kv_heads=64, head_dim=128, norm_eps=1e-6, qk_norm=False,
                   rope_theta=1e4, experts=192, experts_per_token=8,
                   expert_width=2048, block_length=1, mask_id=0,
                   max_len=131072, mla_rope=True, q_lora_rank=1536,
                   kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                   v_head_dim=128,
                   rope_scaling=(32.0, 4096, 32.0, 1.0, 1.0),
                   rope_mscale_all_dim=1.0, dense_layers=1,
                   dense_width=18432, shared_experts=1,
                   router_scores="sigmoid", routed_scale=2.5,
                   expert_groups=8, top_expert_groups=4)

    @classmethod
    def axk1_tiny(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=512, hidden=64, layers=5, heads=4, kv_heads=4,
                   head_dim=16, norm_eps=1e-6, qk_norm=False, rope_theta=1e4,
                   experts=16, experts_per_token=4, expert_width=32,
                   block_length=1, mask_id=0, max_len=4096, mla_rope=True,
                   q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=16,
                   qk_rope_dim=8, v_head_dim=16,
                   rope_scaling=(8.0, 32, 32.0, 1.0, 1.0),
                   rope_mscale_all_dim=1.0, dense_layers=1, dense_width=128,
                   shared_experts=1, router_scores="sigmoid",
                   routed_scale=2.5, expert_groups=4, top_expert_groups=2)

    @classmethod
    def laguna_s_2_1(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=100352, hidden=3072, layers=48, heads=48,
                   kv_heads=8, head_dim=128, norm_eps=1e-6, qk_norm=False,
                   rope_theta=5e5, experts=256, experts_per_token=10,
                   expert_width=1024, block_length=1, mask_id=0,
                   max_len=1048576,
                   layer_types=("full_attention",
                                *("sliding_attention",) * 3) * 12,
                   kv_lora_rank=0, head_gate=True, window=512, swa_heads=72,
                   swa_rope_theta=1e4, rotary_dim=64,
                   rope_scaling=(128.0, 8192, 32.0, 1.0,
                                 1.4852030263919618),
                   dense_layers=1, dense_width=12288, shared_experts=1,
                   router_scores="softmax", routed_scale=2.5)

    @classmethod
    def laguna_tiny(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=512, hidden=64, layers=5, heads=4, kv_heads=2,
                   head_dim=16, norm_eps=1e-6, qk_norm=False, rope_theta=5e5,
                   experts=8, experts_per_token=3, expert_width=32,
                   block_length=1, mask_id=0, max_len=4096,
                   layer_types=("full_attention", "sliding_attention",
                                "sliding_attention", "sliding_attention",
                                "full_attention"),
                   kv_lora_rank=0, head_gate=True, window=9, swa_heads=6,
                   swa_rope_theta=1e4, rotary_dim=8,
                   rope_scaling=(16.0, 32, 32.0, 1.0, 1.2),
                   dense_layers=1, dense_width=128, shared_experts=1,
                   router_scores="softmax", routed_scale=2.5)

    @classmethod
    def dots3_note_prev(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=152064, hidden=5120, layers=46, heads=128,
                   kv_heads=128, head_dim=128, norm_eps=1e-5, qk_norm=False,
                   rope_theta=8e7, experts=256, experts_per_token=8,
                   expert_width=1536, block_length=1, mask_id=0,
                   max_len=524288,
                   layer_types=("full_attention", "full_attention")
                   + ("sliding_attention",) * 3
                   + ("full_attention",
                      *("sliding_attention",) * 3) * 10 + ("full_attention",),
                   mla_rope=True, q_lora_rank=1024, lora_rescale=True,
                   head_gate=True, kv_lora_rank=512, qk_nope_dim=128,
                   qk_rope_dim=64, v_head_dim=128, index_heads=64,
                   index_head_dim=128, index_topk=2048, window=513,
                   swa_heads=64, swa_q_lora_rank=1024, swa_kv_lora_rank=1024,
                   swa_qk_nope_dim=192, swa_qk_rope_dim=64,
                   swa_v_head_dim=128, swa_rope_theta=5e4,
                   dense_layers=1, dense_width=13824, shared_experts=1,
                   router_scores="sigmoid", routed_scale=1.0)

    @classmethod
    def dots3_note_tiny(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=512, hidden=64, layers=5, heads=4, kv_heads=4,
                   head_dim=16, norm_eps=1e-5, qk_norm=False, rope_theta=8e7,
                   experts=8, experts_per_token=2, expert_width=32,
                   block_length=1, mask_id=0, max_len=4096,
                   layer_types=("full_attention", "full_attention",
                                "sliding_attention", "sliding_attention",
                                "sliding_attention", "full_attention"),
                   mla_rope=True, q_lora_rank=24, lora_rescale=True,
                   head_gate=True, kv_lora_rank=32, qk_nope_dim=16,
                   qk_rope_dim=8, v_head_dim=16, index_heads=2,
                   index_head_dim=16, index_topk=16, window=9, swa_heads=2,
                   swa_q_lora_rank=24, swa_kv_lora_rank=48,
                   swa_qk_nope_dim=24, swa_qk_rope_dim=8, swa_v_head_dim=16,
                   swa_rope_theta=5e4, dense_layers=1, dense_width=128,
                   shared_experts=1, router_scores="sigmoid",
                   routed_scale=1.0)

    @classmethod
    def sdar_30b_a3b(cls) -> "DecoderBlockConfig":
        return cls()

    @classmethod
    def kimi_linear_48b_a3b(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=163840, hidden=2304, layers=27, heads=32,
                   kv_heads=32, head_dim=128, norm_eps=1e-5, qk_norm=False,
                   experts=256, experts_per_token=8, expert_width=1024,
                   block_length=1, mask_id=0, max_len=1048576,
                   linear_attn=True,
                   full_attn_layers=(4, 8, 12, 16, 20, 24, 27),
                   dense_layers=1, dense_width=9216, shared_experts=1,
                   router_scores="sigmoid", routed_scale=2.446)

    @classmethod
    def kimi_linear_tiny(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=512, hidden=64, layers=5, heads=4, kv_heads=4,
                   head_dim=16, norm_eps=1e-5, qk_norm=False, experts=8,
                   experts_per_token=2, expert_width=32, block_length=1,
                   mask_id=0, max_len=4096, linear_attn=True,
                   full_attn_layers=(4, 8), gate_rank=8, kv_lora_rank=32,
                   qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                   dense_layers=1, dense_width=128, shared_experts=1,
                   router_scores="sigmoid", routed_scale=2.446)

    @classmethod
    def tiny(cls) -> "DecoderBlockConfig":
        return cls(vocab_size=512, hidden=64, layers=2, heads=4, kv_heads=2,
                   head_dim=16, experts=8, experts_per_token=2,
                   expert_width=32, mask_id=511, max_len=256)

    @property
    def held(self) -> int:
        return self.experts_held or self.experts

    @property
    def vocab(self) -> int:
        """Rows of the embedding and columns of the head held here."""
        return self.vocab_held or self.vocab_size

    @property
    def stateful(self) -> bool:
        """A kind a layer: served by a chunk program and a one-token
        step over the arrays :meth:`BlockDecoder.state_specs` names."""
        return (self.linear_attn or bool(self.layer_types)
                or self.dense_latent)

    def mixer(self, i: int) -> str:
        """Layer ``i``'s mixer (``i`` from 0): ``gqa``, ``kda``, ``mla``,
        ``mla_sparse`` or ``mla_window``, ``gqa_full`` or ``gqa_window``,
        ``mla_dense``. The kind follows from the description: a named
        layer is latent attention where it has an indexer over a latent
        (:attr:`selecting_latent`) and grouped-query attention where it
        has neither; without names, ``linear_attn`` makes it KDA (MLA
        without positions on ``full_attn_layers``), a latent alone at
        one token a step (:attr:`dense_latent`) dense latent attention,
        and nothing at all the block-diffusion ``gqa``."""
        if self.layer_types:
            full = self.layer_types[i] == "full_attention"
            if self.selecting_latent:
                return "mla_sparse" if full else "mla_window"
            return "gqa_full" if full else "gqa_window"
        if self.linear_attn:
            return "mla" if i + 1 in self.full_attn_layers else "kda"
        return "mla_dense" if self.dense_latent else "gqa"

    @property
    def selecting_latent(self) -> bool:
        """Named layers that mix by latent attention under an indexer."""
        return bool(self.layer_types and self.index_topk
                    and self.kv_lora_rank)

    @property
    def dense_latent(self) -> bool:
        """Every layer attends to the whole of a latent cache: a latent,
        one token a step, no recurrent kind and no named layers."""
        return bool(self.block_length == 1 and self.kv_lora_rank
                    and not self.linear_attn and not self.layer_types)

    def heads_of(self, kind: str) -> int:
        """Query heads of a grouped-query layer of ``kind``."""
        return self.swa_heads if kind == "gqa_window" else self.heads

    def geometry(self, kind: str) -> "MlaGeometry":
        """The sizes of an MLA layer of ``kind``."""
        if kind == "mla_window":
            return MlaGeometry(self.swa_heads, self.swa_q_lora_rank,
                               self.swa_kv_lora_rank, self.swa_qk_nope_dim,
                               self.swa_qk_rope_dim, self.swa_v_head_dim,
                               self.swa_rope_theta)
        g = MlaGeometry(self.heads, self.q_lora_rank, self.kv_lora_rank,
                        self.qk_nope_dim, self.qk_rope_dim,
                        self.v_head_dim, self.rope_theta)
        if kind != "mla_dense" or not self.rope_scaling:
            return g
        return dataclasses.replace(
            g, yarn=self.rope_scaling,
            mscale=0.1 * self.rope_mscale_all_dim * math.log(
                self.rope_scaling[0]) + 1.0)

    def layers_of(self, kind: str) -> list[int]:
        return [i for i in range(self.layers) if self.mixer(i) == kind]

    def rows_of(self, kind: str) -> dict[int, int]:
        """Layer -> its row in the state arrays of ``kind``'s layers."""
        return {i: j for j, i in enumerate(self.layers_of(kind))}

    def state_rows(self) -> tuple[dict, dict]:
        """Layer -> its row in the recurrent arrays (KDA layers) and in
        the latent pool (MLA layers)."""
        return self.rows_of("kda"), self.rows_of("mla")

    @property
    def ring(self) -> int:
        """Rows of a sliding layer's ring a slot."""
        return mla_ops.ring_rows(self.window)

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_row(self) -> int:
        """A token's row of the latent pool: ``latent_dim`` values and
        zeros up to whole 128-lane tiles. (The chip lays a [.., Bs, 576]
        array out tokens-minor so as not to pad it, and every program
        that reads it rows-minor then copies the whole pool.)"""
        return -(-self.latent_dim // 128) * 128

    @property
    def conv_channels(self) -> int:
        return 3 * self.heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class MlaGeometry:
    """One MLA layer kind's sizes (a model may hold two)."""
    heads: int
    q_rank: int                     # 0: one query matrix
    rank: int
    nope: int
    pe: int
    v: int
    theta: float
    #: YaRN on the rope values: ``DecoderBlockConfig.rope_scaling``
    yarn: tuple = ()
    #: YaRN's ``mscale(factor, mscale_all_dim)``; the scores carry it
    #: twice (q and k)
    mscale: float = 1.0

    @property
    def row(self) -> int:
        return mla_ops.latent_row(self.rank, self.pe)

    @property
    def scale(self) -> float:
        # (x 1.0 where nothing scales the context: the plain scale, exactly)
        return (self.nope + self.pe) ** -0.5 * self.mscale ** 2


#: a grouped-query description's caches (state_specs): the paged K/V
#: pool of its full layers, the rings of its window layers
_KV_ARRAYS = ("cache_k", "cache_v", "cache_window_k", "cache_window_v")

#: the parameter group a layer's mixer lies under, where not its kind
_GROUP = {"gqa": "attn", "mla_sparse": "mla", "mla_window": "mla",
          "mla_dense": "mla", "gqa_full": "attn", "gqa_window": "attn"}


def _rms(x, scale, eps: float):
    """RMSNorm over the last axis in float32 (x and the result)."""
    x = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * inv * scale.astype(jnp.float32)


def _rope(x, pos, theta: float, *, width: int = 0, inv_freq=None,
          factor: float = 1.0):
    """Rotary positions, rotate-half over the leading ``width`` values of
    a head (0: the whole head): ``x`` [T, H, D] float32, ``pos`` [T]
    int32. ``inv_freq`` [width / 2]: a table of inverse frequencies in
    the place of ``theta``'s own; ``factor`` multiplies cos and sin
    (YaRN's attention factor)."""
    d = width or x.shape[-1]
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    if d == x.shape[-1]:
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    xr, x1, x2 = x[..., :d], x[..., :d // 2], x[..., d // 2:d]
    return jnp.concatenate(
        [xr * cos + jnp.concatenate([-x2, x1], axis=-1) * sin, x[..., d:]],
        axis=-1)


class BlockDecoder(DefaultRulesMixin):
    name = "sdar_moe"

    def __init__(self, cfg: DecoderBlockConfig, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16):
        named_gqa = cfg.layer_types and not cfg.selecting_latent
        for heads in (cfg.heads, cfg.swa_heads) if named_gqa else (
                cfg.heads,):
            if not heads or heads % cfg.kv_heads:
                raise ValueError(f"{heads} query heads do not divide over "
                                 f"{cfg.kv_heads} KV heads")
        b = cfg.block_length
        if b < 1 or b & (b - 1):
            raise ValueError(f"block_length must be a power of two, got {b}")
        if cfg.first_expert + cfg.held > cfg.experts:
            raise ValueError(
                f"experts {cfg.first_expert}..{cfg.first_expert + cfg.held}"
                f" are not among the {cfg.experts} the router knows")
        if cfg.stateful != (b == 1):
            raise ValueError(
                "a kind a layer (linear_attn, layer_types or a latent "
                "alone) and one token a step (block_length = 1) come "
                "together: the "
                "block-diffusion forwards run grouped-query attention "
                "only, under one head count and no window; got "
                f"linear_attn={cfg.linear_attn}, layer_types of "
                f"{len(cfg.layer_types)}, block_length={b}")
        if cfg.layer_types:
            if cfg.linear_attn or len(cfg.layer_types) < cfg.layers:
                raise ValueError(
                    f"layer_types names {len(cfg.layer_types)} layers of "
                    f"{cfg.layers} (and excludes linear_attn)")
            if not cfg.window:
                raise ValueError("layer_types needs window (the rows a "
                                 "sliding_attention layer sees)")
            if bool(cfg.index_topk) != bool(cfg.index_heads):
                raise ValueError(
                    "an indexer needs index_topk and index_heads (latent "
                    "attention under a selection); without either the "
                    f"named layers are grouped-query; got index_topk="
                    f"{cfg.index_topk}, index_heads={cfg.index_heads}")
            if cfg.index_topk and not cfg.kv_lora_rank:
                raise ValueError(
                    "a selection over K/V heads is not built: an indexer "
                    "(index_topk) needs the latent cache (kv_lora_rank)")
            if cfg.rotary_dim % 2 or cfg.rotary_dim > cfg.head_dim:
                raise ValueError(f"rotary_dim {cfg.rotary_dim} is not an "
                                 f"even part of a head of {cfg.head_dim}")
        if cfg.dense_latent and not cfg.q_lora_rank:
            raise ValueError(
                "dense latent attention takes its queries from a low-rank "
                "pair (q_lora_rank); one query matrix is linear_attn's "
                "MLA on full_attn_layers")
        if cfg.rope_scaling and len(cfg.rope_scaling) != 5:
            raise ValueError(
                "rope_scaling is (factor, original context, beta_fast,"
                f" beta_slow, attention factor), got {cfg.rope_scaling}")
        if cfg.expert_groups > 1 and (
                cfg.router_scores != "sigmoid"
                or cfg.experts % cfg.expert_groups
                or not 0 < cfg.top_expert_groups <= cfg.expert_groups):
            raise ValueError(
                f"a group limit ({cfg.top_expert_groups} of "
                f"{cfg.expert_groups} groups) needs the sigmoid router "
                f"and {cfg.experts} experts in whole groups")
        if cfg.router_scores not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_scores {cfg.router_scores!r}")
        if cfg.first_vocab + cfg.vocab > cfg.vocab_size:
            raise ValueError(
                f"ids {cfg.first_vocab}..{cfg.first_vocab + cfg.vocab} are "
                f"not among the {cfg.vocab_size} of the vocabulary")
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
        keys = iter(jax.random.split(
            rng, 2 + (24 if c.stateful else 8) * c.layers))
        qd, kd = c.heads * c.head_dim, c.kv_heads * c.head_dim

        def glorot(*shape):
            std = math.sqrt(2.0 / (shape[-2] + shape[-1]))
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(self.param_dtype)

        def ones(n):
            return jnp.ones((n,), self.param_dtype)

        def uniform(lo, hi, *shape):
            return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

        params = {
            "embed": (jax.random.normal(next(keys), (c.vocab, c.hidden),
                                        jnp.float32) * 0.02
                      ).astype(self.param_dtype),
            "layers": {},
            "norm_f": ones(c.hidden),
            "head": glorot(c.hidden, c.vocab),
        }

        def gated(width, *lead):
            return {"gate": glorot(*lead, c.hidden, width),
                    "up": glorot(*lead, c.hidden, width),
                    "down": glorot(*lead, width, c.hidden)}

        def mixer(kind):
            if kind in ("gqa_full", "gqa_window"):
                hd = c.heads_of(kind) * c.head_dim
                ap = {"wq": glorot(c.hidden, hd), "wk": glorot(c.hidden, kd),
                      "wv": glorot(c.hidden, kd), "wo": glorot(hd, c.hidden)}
                if c.head_gate:
                    ap["wg"] = glorot(c.hidden, c.heads_of(kind))
                if c.qk_norm:
                    ap.update(q_norm=ones(c.head_dim),
                              k_norm=ones(c.head_dim))
                return ap
            if kind == "gqa":
                return {"wq": glorot(c.hidden, qd),
                        "wk": glorot(c.hidden, kd),
                        "wv": glorot(c.hidden, kd),
                        "wo": glorot(qd, c.hidden),
                        "q_norm": ones(c.head_dim),
                        "k_norm": ones(c.head_dim)}
            if kind in ("mla_sparse", "mla_window", "mla_dense"):
                g = c.geometry(kind)
                mp = {"wqa": glorot(c.hidden, g.q_rank),
                      "q_norm": ones(g.q_rank),
                      "wqb": glorot(g.q_rank, g.heads * (g.nope + g.pe)),
                      "wkva": glorot(c.hidden, g.rank + g.pe),
                      "kv_norm": ones(g.rank),
                      "wkvb": glorot(g.rank, g.heads * (g.nope + g.v)),
                      "wg": glorot(c.hidden, g.heads),
                      "wo": glorot(g.heads * g.v, c.hidden)}
                if not c.head_gate:
                    del mp["wg"]
                if kind == "mla_sparse":
                    mp["index"] = {
                        "wq": glorot(g.q_rank,
                                     c.index_heads * c.index_head_dim),
                        "wk": glorot(c.hidden, c.index_head_dim),
                        "k_scale": ones(c.index_head_dim),
                        "k_bias": jnp.zeros((c.index_head_dim,),
                                            self.param_dtype),
                        "ww": glorot(c.hidden, c.index_heads)}
                return mp
            if kind == "mla":
                return {"wq": glorot(c.hidden, c.heads * (
                            c.qk_nope_dim + c.qk_rope_dim)),
                        "wkva": glorot(c.hidden, c.latent_dim),
                        "kv_norm": ones(c.kv_lora_rank),
                        "wkvb": glorot(c.kv_lora_rank, c.heads * (
                            c.qk_nope_dim + c.v_head_dim)),
                        "wo": glorot(c.heads * c.v_head_dim, c.hidden)}
            # kda: the family's initial ranges for the decay: exp(a_log)
            # in [1, 16], softplus(dt_bias) log-uniform in [0.001, 0.1]
            dt = jnp.exp(uniform(math.log(1e-3), math.log(0.1), qd))
            return {"wqkv": glorot(c.hidden, c.conv_channels),
                    "conv": glorot(c.conv_kernel, c.conv_channels),
                    "f_a": glorot(c.hidden, c.gate_rank),
                    "f_b": glorot(c.gate_rank, qd),
                    # float32 whatever the storage: a few thousand
                    # values that enter two exponentials
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "a_log": jnp.log(uniform(1.0, 16.0, c.heads)),
                    "wb": glorot(c.hidden, c.heads),
                    "g_a": glorot(c.hidden, c.gate_rank),
                    "g_b": glorot(c.gate_rank, qd),
                    "o_norm": ones(c.head_dim),
                    "wo": glorot(qd, c.hidden)}

        for i in range(c.layers):
            kind = c.mixer(i)
            lp = {"attn_norm": ones(c.hidden),
                  _GROUP.get(kind, kind): mixer(kind),
                  "ffn_norm": ones(c.hidden)}
            if i < c.dense_layers:
                lp["mlp"] = gated(c.dense_width)
            else:
                lp["moe"] = {"router": glorot(c.hidden, c.experts),
                             **gated(c.expert_width, c.held)}
                if c.router_scores == "sigmoid":
                    lp["moe"]["router_bias"] = (jax.random.normal(
                        next(keys), (c.experts,), jnp.float32) * 0.02
                    ).astype(self.param_dtype)
                if c.shared_experts:
                    lp["moe"]["shared"] = gated(
                        c.shared_experts * c.expert_width)
            params["layers"][str(i)] = lp
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

    # ------------------------------------------------------------------
    # one token a step: a kind a layer (linear_attn)
    # ------------------------------------------------------------------
    def state_specs(self, *, slots: int, num_blocks: int,
                    block_size: int) -> dict:
        """What a server keeps for this model between dispatches, by
        layer kind: ``{name: {"shape", "dtype", "layers", "per"}}``.
        ``per = "block"``: rows behind the block tables, allocated and
        released with a request's blocks, never zeroed (a row is written
        before it is read). ``per = "slot"``: one row a slot, zeroed when
        a request takes the slot, carried from chunk to chunk to the
        decode steps, and left as it lies at release."""
        c = self.cfg
        if c.layer_types and not c.selecting_latent:
            full, win = c.layers_of("gqa_full"), c.layers_of("gqa_window")
            dt = str(jnp.dtype(self.dtype))
            row = c.kv_heads * c.head_dim   # a token's KV heads, side by side
            pool = {"shape": [len(full), num_blocks, block_size, row],
                    "dtype": dt, "layers": full, "per": "block"}
            # the last `window` rows of a slot, row p % ring holding
            # position p: bounded, whatever the context
            ring = {"shape": [len(win), slots, c.ring, row],
                    "dtype": dt, "layers": win, "per": "slot"}
            return {"cache_k": pool, "cache_v": dict(pool),
                    "cache_window_k": ring, "cache_window_v": dict(ring)}
        if c.layer_types:
            full, win = c.layers_of("mla_sparse"), c.layers_of("mla_window")
            dt = str(jnp.dtype(self.dtype))
            return {
                "cache_latent": {
                    "shape": [len(full), num_blocks, block_size,
                              c.geometry("mla_sparse").row],
                    "dtype": dt, "layers": full, "per": "block"},
                # a token's index key, behind the same block tables
                "cache_index": {
                    "shape": [len(full), num_blocks, block_size,
                              c.index_head_dim],
                    "dtype": dt, "layers": full, "per": "block"},
                # the last `window` latent rows of a slot, row p % ring
                # holding position p: bounded, whatever the context
                "cache_window": {
                    "shape": [len(win), slots, c.ring,
                              c.geometry("mla_window").row],
                    "dtype": dt, "layers": win, "per": "slot"},
            }
        if c.dense_latent:
            # the latent pool is the whole of a request's state
            every = c.layers_of("mla_dense")
            return {"cache_latent": {
                "shape": [len(every), num_blocks, block_size,
                          c.geometry("mla_dense").row],
                "dtype": str(jnp.dtype(self.dtype)), "layers": every,
                "per": "block"}}
        kda, mla = c.layers_of("kda"), c.layers_of("mla")
        return {
            "cache_latent": {
                "shape": [len(mla), num_blocks, block_size, c.latent_row],
                "dtype": str(jnp.dtype(self.dtype)), "layers": mla,
                "per": "block"},
            "cache_state": {
                "shape": [len(kda), slots, c.heads, c.head_dim, c.head_dim],
                "dtype": "float32", "layers": kda, "per": "slot"},
            # the K - 1 inputs before a slot's next token, oldest first,
            # side by side in one row (3 rows of their own in the tiled
            # dimensions would be padded to 8, or laid out slots-minor)
            "cache_conv": {
                "shape": [len(kda), slots,
                          (c.conv_kernel - 1) * c.conv_channels],
                "dtype": "float32", "layers": kda, "per": "slot"},
        }

    def _mm32(self, x, w):
        """float32 operands at ``highest`` precision: KDA's decay, rate
        and gate (small products whose results enter an exponential)."""
        return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST)

    def _embed(self, params, ids):
        """Rows of the held slice; an id held elsewhere embeds as zeros
        here (its row arrives from the chip that holds it)."""
        c = self.cfg
        local = ids - c.first_vocab
        mine = (local >= 0) & (local < c.vocab)
        rows = params["embed"][jnp.clip(local, 0, c.vocab - 1)]
        return jnp.where(mine[:, None], rows.astype(jnp.float32), 0.0)

    def _greedy(self, params, h, with_logits: bool):
        """The held slice's best id (as an id of the whole vocabulary)."""
        c = self.cfg
        with jax.named_scope("sample"):
            logits = self._mm(_rms(h, params["norm_f"], c.norm_eps),
                              params["head"])               # [T, V] f32
            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32) \
                + c.first_vocab
        return ids, (logits if with_logits else None)

    def _kda_inputs(self, kp, n, conv_in):
        """What KDA's recurrence takes, from the normed rows ``n`` [T, h]
        and the convolution of their projections ``conv_in`` [T, 3 H d]
        (already through the short convolution): ``q, k, v`` [T, H, d],
        ``log_a`` [T, H, d] (<= 0) and ``b`` [T, H], float32."""
        c = self.cfg
        t = n.shape[0]
        hd = (t, c.heads, c.head_dim)
        q, k, v = jnp.split(jax.nn.silu(conv_in), 3, axis=-1)
        q = kda_ops.l2norm(q.reshape(hd)) * c.head_dim ** -0.5
        k = kda_ops.l2norm(k.reshape(hd))
        f = self._mm32(self._mm32(n, kp["f_a"]), kp["f_b"]) \
            + kp["dt_bias"].astype(jnp.float32)
        log_a = -jnp.exp(kp["a_log"].astype(jnp.float32))[None, :, None] \
            * jax.nn.softplus(f).reshape(hd)
        b = jax.nn.sigmoid(self._mm32(n, kp["wb"]))
        return q, k, v.reshape(hd), log_a, b

    def _kda_output(self, kp, n, o):
        """``W_o [ RMSNorm_d(o) * sigmoid(W_g2 W_g1 n) ]``."""
        c = self.cfg
        t = n.shape[0]
        gate = jax.nn.sigmoid(self._mm32(self._mm32(n, kp["g_a"]),
                                         kp["g_b"]))
        o = _rms(o, kp["o_norm"], c.norm_eps).reshape(t, -1) * gate
        return self._mm(o, kp["wo"])

    def _mla_latent(self, mp, n):
        """The row a token keeps: ``[RMSNorm(c[:rank]) ; c[rank:]]``."""
        c = self.cfg
        ckv = self._mm(n, mp["wkva"])
        return jnp.concatenate(
            [_rms(ckv[:, :c.kv_lora_rank], mp["kv_norm"], c.norm_eps),
             ckv[:, c.kv_lora_rank:],
             jnp.zeros((n.shape[0], c.latent_row - c.latent_dim))],
            axis=-1).astype(self.dtype)

    def _typed_q(self, mp, n, pos, g: MlaGeometry):
        """A typed MLA layer's queries from the normed rows ``n`` [T, h]:
        the query latent ``c_q`` [T, q_rank] float32 (normed, rescaled:
        what the indexer reads too) and ``q`` [T, H, nope + pe] float32
        with rotary positions on the last ``pe`` values."""
        c = self.cfg
        t = n.shape[0]
        cq = _rms(self._mm(n, mp["wqa"]), mp["q_norm"], c.norm_eps)
        if c.lora_rescale:
            cq = cq * math.sqrt(c.hidden / g.q_rank)
        q = self._mm(cq, mp["wqb"]).reshape(t, g.heads, g.nope + g.pe)
        if c.mla_rope:
            q = jnp.concatenate(
                [q[..., :g.nope], self._latent_rope(q[..., g.nope:], pos, g)],
                axis=-1)
        return cq, q

    def _latent_rope(self, x, pos, g: MlaGeometry):
        """Rotary positions on the rope values of a latent layer's heads
        ``x`` [T, H, pe]: at YaRN's frequencies where the geometry scales
        them (a method so that a planted fault can leave the scaling
        off)."""
        if not g.yarn:
            return _rope(x, pos, g.theta)
        factor, original, fast, slow, attn = g.yarn
        return _rope(x, pos, g.theta, inv_freq=jnp.asarray(
            gqa_ops.yarn_inv_freq(g.pe, g.theta, factor, original, fast,
                                  slow)), factor=attn)

    def _typed_latent(self, mp, n, pos, g: MlaGeometry):
        """The row a token keeps in a typed MLA layer: ``[RMSNorm(c[:rank])
        (rescaled) ; rope(c[rank:]) ; zeros to whole lane tiles]``."""
        c = self.cfg
        ckv = self._mm(n, mp["wkva"])
        lat = _rms(ckv[:, :g.rank], mp["kv_norm"], c.norm_eps)
        if c.lora_rescale:
            lat = lat * math.sqrt(c.hidden / g.rank)
        k_pe = ckv[:, g.rank:]
        if c.mla_rope:
            k_pe = (self._rope_k(k_pe, pos, g.theta, g) if g.yarn
                    else self._rope_k(k_pe, pos, g.theta))
        return jnp.concatenate(
            [lat, k_pe, jnp.zeros((n.shape[0], g.row - g.rank - g.pe))],
            axis=-1).astype(self.dtype)

    def _rope_k(self, k_pe, pos, theta, g: MlaGeometry | None = None):
        """Rotary positions on the one ``k_pe`` a token's heads share
        (a method so that a planted fault can leave it off); ``g``: the
        geometry whose YaRN table replaces ``theta``'s own."""
        if g is not None:
            return self._latent_rope(k_pe[:, None, :], pos, g)[:, 0]
        return _rope(k_pe[:, None, :], pos, theta)[:, 0]

    def _step_pool(self, before, after):
        """The latent pool a step's absorbed attention reads: the one
        with the step's own rows written (a method so that a planted
        fault can hand it the one before)."""
        del before
        return after

    def _index_inputs(self, ip, n, cq, pos):
        """The indexer's three inputs for rows ``n`` [T, h]: queries
        [T, J, D] and this token's key [T, D] in ``dtype`` (the leading
        ``qk_rope_dim`` values of each rotated), and the heads' weights
        [T, J] float32 (``J^-1/2 D^-1/2`` folded in)."""
        c = self.cfg
        t = n.shape[0]
        j, d, pe = c.index_heads, c.index_head_dim, c.qk_rope_dim

        def rot(x):                         # [T, heads, D]
            return jnp.concatenate(
                [_rope(x[..., :pe], pos, c.rope_theta), x[..., pe:]],
                axis=-1)

        q = rot(self._mm(cq, ip["wq"]).reshape(t, j, d))
        k = self._mm(n, ip["wk"])
        mu = jnp.mean(k, axis=-1, keepdims=True)
        k = (k - mu) * lax.rsqrt(
            jnp.mean(jnp.square(k - mu), axis=-1, keepdims=True)
            + c.norm_eps) * ip["k_scale"].astype(jnp.float32) \
            + ip["k_bias"].astype(jnp.float32)
        k = rot(k[:, None, :])[:, 0]
        w = self._mm(n, ip["ww"]) * (j ** -0.5 * d ** -0.5)
        return q.astype(self.dtype), k.astype(self.dtype), w

    def _absorb(self, mp, q, g: MlaGeometry):
        """``W_kvb`` folded into one-token queries: ``q`` [S, H, nope +
        pe] float32 -> the absorbed query [S, H, row] in ``dtype``
        (scaled) and ``W_vb`` [rank, H, v] for the way out."""
        s = q.shape[0]
        q = q * g.scale
        wkvb = mp["wkvb"].reshape(g.rank, g.heads, -1)
        q_lat = jnp.einsum(
            "shd,chd->shc", q[..., :g.nope].astype(self.dtype),
            wkvb[..., :g.nope].astype(self.dtype),
            preferred_element_type=jnp.float32)
        q_abs = jnp.concatenate(
            [q_lat, q[..., g.nope:],
             jnp.zeros((s, g.heads, g.row - g.rank - g.pe))], axis=-1)
        return q_abs.astype(self.dtype), wkvb[..., g.nope:]

    def _unabsorb(self, ctx, w_vb):
        """The heads' outputs from the weighted latent rows: ``ctx``
        [S, H, rank] float32, ``W_vb`` [rank, H, v] -> [S, H, v]."""
        return jnp.einsum("shc,chd->shd", ctx.astype(self.dtype),
                          w_vb.astype(self.dtype),
                          preferred_element_type=jnp.float32)

    def _gate_heads(self, mp, n, ctx):
        """``g_h . ctx_h`` with ``g = sigmoid(W_g n)`` (a method so that a
        planted fault can drop the gate)."""
        return ctx * jax.nn.sigmoid(self._mm(n, mp["wg"]))[..., None]

    def _typed_out(self, mp, n, ctx):
        """``W_o concat_h(g_h . ctx_h)``: ``ctx`` [T, H, v] float32."""
        t = n.shape[0]
        if self.cfg.head_gate:
            ctx = self._gate_heads(mp, n, ctx)
        return self._mm(ctx.reshape(t, -1), mp["wo"])

    def _full_rope(self, x, pos):
        """Rotary positions of a grouped-query full layer: the leading
        ``rotary_dim`` values of a head, at YaRN's frequencies where the
        description scales them (a method so that a planted fault can
        rotate the whole head at the plain ones)."""
        c = self.cfg
        if not c.rope_scaling:
            return _rope(x, pos, c.rope_theta, width=c.rotary_dim)
        factor, original, fast, slow, attn = c.rope_scaling
        return _rope(x, pos, c.rope_theta, width=c.rotary_dim,
                     inv_freq=jnp.asarray(gqa_ops.yarn_inv_freq(
                         c.rotary_dim or c.head_dim, c.rope_theta, factor,
                         original, fast, slow)), factor=attn)

    def _gqa_qkv(self, ap, n, pos, kind: str):
        """A grouped-query layer's inputs from the normed rows ``n``
        [T, h]: q [T, H, D] (H the kind's own), k and v [T, KVH x D] as
        the caches keep them, in ``dtype``, q and k rotated."""
        c = self.cfg
        t = n.shape[0]
        q = self._mm(n, ap["wq"]).reshape(t, -1, c.head_dim)
        k = self._mm(n, ap["wk"]).reshape(t, c.kv_heads, c.head_dim)
        v = self._mm(n, ap["wv"])
        if c.qk_norm:
            q = _rms(q, ap["q_norm"], c.norm_eps)
            k = _rms(k, ap["k_norm"], c.norm_eps)
        if kind == "gqa_full":
            q, k = self._full_rope(q, pos), self._full_rope(k, pos)
        else:
            q = _rope(q, pos, c.swa_rope_theta)
            k = _rope(k, pos, c.swa_rope_theta)
        return (self._heads_in(q.astype(self.dtype)),
                k.reshape(t, -1).astype(self.dtype), v.astype(self.dtype))

    def _heads_in(self, q):
        """The query heads in the order the attention groups them: head
        ``j`` reads KV head ``j // (H / KVH)`` (a method, with
        :meth:`_heads_out`, so that a planted fault can deal them
        otherwise)."""
        return q

    def _heads_out(self, ctx):
        return ctx

    def _expert_groups(self) -> int:
        """Groups the sigmoid router's choice is limited by (a method so
        that a planted fault can drop the limit)."""
        return self.cfg.expert_groups

    def _routed_scale(self) -> float:
        """What the renormalised picks' weights sum to (a method so that
        a planted fault can leave it off)."""
        return self.cfg.routed_scale

    def _chunk_index(self, index, j, blocks, keys):
        """The index pool with a chunk's keys in its blocks (a method so
        that a planted fault can leave what the blocks held)."""
        return index.at[j, blocks].set(keys)

    def _select_rows(self, scores, k: int):
        """The selected positions of one row a slot (a method, as
        :meth:`_select`)."""
        return dsa_ops.top_k_rows(scores, k)

    def _select(self, scores, k: int, live):
        """The selection's mask for a chunk's rows, no candidate from
        column ``live`` on (a method so that a planted fault can drop
        it)."""
        return dsa_ops.top_k_mask(scores, k, live=live)

    def _ffn_of(self, i, lp, h, routed: list | None = None, counted=None):
        """Layer ``i``'s FFN on the residual stream; the held experts
        that received a row, per expert (None for a dense layer).
        ``routed``: a list that takes, for a sparse layer, how many of
        the rows ``counted`` ([T] bool) picked at least one held
        expert."""
        c = self.cfg
        m = _rms(h, lp["ffn_norm"], c.norm_eps)

        def gated(p, x):
            with jax.named_scope("dense_ffn"):
                act = jax.nn.silu(self._mm(x, p["gate"])) * self._mm(
                    x, p["up"])
                return self._mm(act, p["down"])

        if "mlp" in lp:
            return h + gated(lp["mlp"], m), None
        mp = lp["moe"]
        y, rows, *count = moe_dropless(
            m, mp["router"], mp, top_k=c.experts_per_token,
            first_expert=c.first_expert, dtype=self.dtype,
            router_dtype=self.router_dtype, scores=c.router_scores,
            select_bias=mp.get("router_bias"), scale=self._routed_scale(),
            groups=self._expert_groups(), top_groups=c.top_expert_groups,
            count_routed=None if routed is None else counted)
        if count:
            routed.append(count[0])
        if "shared" in mp:
            y = y + gated(mp["shared"], m)
        return h + y, rows

    def prefill_chunk(self, params, state, input_ids, n_valid, start, slot,
                      table_row, chunk_blocks, *, with_logits: bool = False,
                      kda_chunk: int = kda_ops.SUB_CHUNK,
                      attention: str = "auto"):
        """One chunk of one prompt: ``C`` tokens from what the chunks
        before it left (the slot's KDA state and conv tails, the latent
        rows in its blocks) to what the next chunk, or the first decode
        step, starts from.

        ``state``: the three arrays of :meth:`state_specs`; ``input_ids``
        [1, C], of which the first ``n_valid`` are tokens (positions
        ``start .. start + n_valid - 1``; ``start`` a multiple of C);
        ``slot`` the request's slot; ``table_row`` [NBp] its blocks;
        ``chunk_blocks`` [C / Bs] the blocks this chunk's rows go to (the
        null block 0 past the prompt's run). Padding leaves the recurrent
        rows as they were; its latent rows land where a decode step
        overwrites them before any read. ``attention``: the selected
        attention of a ``full_attention`` layer, ``"auto"`` (the Pallas
        kernel on a TPU where the shapes allow), ``"pallas"`` or ``"xla"``
        (``ops/mla.mla_masked_prefill_attention``). Returns the state,
        ``ids`` [1]: the greedy token after the chunk's last token, and,
        where the expert layers run over a bound of the chunk's pairs
        (``ops/moe.pair_bound``), ``moe_whole``: how many of them passed
        it and ran at the whole width."""
        c = self.cfg
        cw = input_ids.shape[1]
        # the arrays of the kinds this model has (state_specs)
        latent = state.get("cache_latent")
        s_all, conv_all = state.get("cache_state"), state.get("cache_conv")
        index, rings = state.get("cache_index"), state.get("cache_window")
        kv = {k: state[k] for k in _KV_ARRAYS if k in state}
        n_mla, nb, bs, r = (kv["cache_k"] if kv else latent).shape
        flat = (n_mla * nb, bs, r)      # a layer's blocks, nb further on
        table_row = jnp.asarray(table_row, jnp.int32)
        chunk_blocks = jnp.asarray(chunk_blocks, jnp.int32)
        valid = jnp.arange(cw) < n_valid
        h = self._embed(params, input_ids[0])
        expert_rows = jnp.zeros((), jnp.int32)
        # the expert layers whose held pairs passed the bound they run
        # over (ops/moe.pair_bound): counted where there is one
        pairs = cw * c.experts_per_token
        bound = pair_bound(pairs, c.held, c.experts)
        bounded = bound < pairs
        moe_whole = jnp.zeros((), jnp.int32)
        kda_at, mla_at = c.state_rows()
        sparse_at, window_at = (c.rows_of("mla_sparse"),
                                c.rows_of("mla_window"))
        gfull_at, gwin_at = c.rows_of("gqa_full"), c.rows_of("gqa_window")
        dense_at = c.rows_of("mla_dense")
        # under a group limit: the prompt's rows that picked a held expert,
        # by layer
        routed = [] if c.expert_groups > 1 else None
        pos = start + jnp.arange(cw, dtype=jnp.int32)
        for i in range(c.layers):
            lp = params["layers"][str(i)]
            n = _rms(h, lp["attn_norm"], c.norm_eps)
            if i in gfull_at:
                j, ap = gfull_at[i], lp["attn"]
                with jax.named_scope("gqa_full"):
                    q, k, v = self._gqa_qkv(ap, n, pos, "gqa_full")
                    for name, rows in (("cache_k", k), ("cache_v", v)):
                        kv[name] = kv[name].at[j, chunk_blocks].set(
                            rows.reshape(cw // bs, bs, r))
                    ctx = gqa_ops.gqa_prefill_attention(
                        q, kv["cache_k"].reshape(flat),
                        kv["cache_v"].reshape(flat), table_row + j * nb,
                        start, key_tile=min(1024, cw), impl=attention)
                    h = h + self._typed_out(ap, n, self._heads_out(ctx))
            elif i in gwin_at:
                j, ap = gwin_at[i], lp["attn"]
                with jax.named_scope("gqa_window"):
                    q, k, v = self._gqa_qkv(ap, n, pos, "gqa_window")
                    ctx = gqa_ops.gqa_window_prefill_attention(
                        q, k, v, kv["cache_window_k"][j, slot],
                        kv["cache_window_v"][j, slot], start,
                        window=self._window(), block_size=bs,
                        impl=attention)
                    for name, rows in (("cache_window_k", k),
                                       ("cache_window_v", v)):
                        kv[name] = kv[name].at[j, slot].set(
                            mla_ops.ring_after_chunk(
                                kv[name][j, slot], rows, start, n_valid))
                    h = h + self._typed_out(ap, n, self._heads_out(ctx))
            elif i in sparse_at:
                j, mp = sparse_at[i], lp["mla"]
                g = c.geometry("mla_sparse")
                with jax.named_scope("mla_sparse"):
                    cq, q = self._typed_q(mp, n, pos, g)
                    latent = latent.at[j, chunk_blocks].set(
                        self._typed_latent(mp, n, pos, g).reshape(
                            cw // bs, bs, r))
                    qi, ki, wi = self._index_inputs(mp["index"], n, cq, pos)
                    index = self._chunk_index(
                        index, j, chunk_blocks, ki.reshape(cw // bs, bs, -1))
                    scores = dsa_ops.chunk_scores(
                        qi, wi, index.reshape(n_mla * nb, bs, -1),
                        table_row + j * nb, start,
                        width=table_row.shape[0] * bs)
                    ctx = mla_ops.mla_masked_prefill_attention(
                        q, latent.reshape(flat), table_row + j * nb, start,
                        mp["wkvb"].reshape(g.rank, g.heads, -1),
                        self._select(scores, c.index_topk, start + cw),
                        rank=g.rank, nope=g.nope, pe=g.pe, v_dim=g.v,
                        scale=g.scale, impl=attention)
                    h = h + self._typed_out(mp, n, ctx)
            elif i in window_at:
                j, mp = window_at[i], lp["mla"]
                g = c.geometry("mla_window")
                with jax.named_scope("mla_window"):
                    _, q = self._typed_q(mp, n, pos, g)
                    lat = self._typed_latent(mp, n, pos, g)
                    ctx = mla_ops.mla_window_prefill_attention(
                        q, lat, rings[j, slot], start,
                        mp["wkvb"].reshape(g.rank, g.heads, -1),
                        window=self._window(), rank=g.rank, nope=g.nope,
                        pe=g.pe, v_dim=g.v, scale=g.scale)
                    rings = rings.at[j, slot].set(mla_ops.ring_after_chunk(
                        rings[j, slot], lat, start, n_valid))
                    h = h + self._typed_out(mp, n, ctx)
            elif i in dense_at:
                j, mp = dense_at[i], lp["mla"]
                g = c.geometry("mla_dense")
                with jax.named_scope("mla_dense"):
                    _, q = self._typed_q(mp, n, pos, g)
                    latent = latent.at[j, chunk_blocks].set(
                        self._typed_latent(mp, n, pos, g).reshape(
                            cw // bs, bs, r))
                    # expanded: a head's K and V from a tile of rows
                    ctx = mla_ops.mla_chunk_attention(
                        q, latent.reshape(flat), table_row + j * nb, start,
                        mp["wkvb"].reshape(g.rank, g.heads, -1),
                        rank=g.rank, nope=g.nope, pe=g.pe, v_dim=g.v,
                        scale=g.scale, impl=attention)
                    h = h + self._typed_out(mp, n, ctx)
            elif i in kda_at:
                j, kp = kda_at[i], lp["kda"]
                with jax.named_scope("kda"):
                    y, tail = kda_ops.causal_conv(
                        self._mm(n, kp["wqkv"]),
                        conv_all[j, slot].reshape(c.conv_kernel - 1, -1),
                        kp["conv"], n_valid)
                    q, k, v, log_a, b = self._kda_inputs(kp, n, y)
                    log_a = jnp.where(valid[:, None, None], log_a, 0.0)
                    b = jnp.where(valid[:, None], b, 0.0)
                    o, s_new = kda_ops.kda_chunk_scan(
                        s_all[j, slot], q, k, v, log_a, b,
                        chunk=min(kda_chunk, cw))
                    s_all = s_all.at[j, slot].set(s_new)
                    conv_all = conv_all.at[j, slot].set(tail.reshape(-1))
                    h = h + self._kda_output(kp, n, o)
            else:
                j, mp = mla_at[i], lp["mla"]
                with jax.named_scope("mla"):
                    lat = self._mla_latent(mp, n)
                    latent = latent.at[j, chunk_blocks].set(
                        lat.reshape(cw // bs, bs, r))
                    q = self._mm(n, mp["wq"]).reshape(
                        cw, c.heads, c.qk_nope_dim + c.qk_rope_dim)
                    ctx = mla_ops.mla_prefill_attention(
                        q, latent.reshape(flat), table_row + j * nb, start,
                        mp["wkvb"].reshape(c.kv_lora_rank, c.heads, -1),
                        rank=c.kv_lora_rank, nope=c.qk_nope_dim,
                        pe=c.qk_rope_dim, v_dim=c.v_head_dim,
                        scale=(c.qk_nope_dim + c.qk_rope_dim) ** -0.5)
                    h = h + self._mm(ctx.reshape(cw, -1), mp["wo"])
            h, rows = self._ffn_of(i, lp, h, routed, valid)
            if rows is not None:
                expert_rows += jnp.sum(rows > 0).astype(jnp.int32)
                if bounded:
                    moe_whole += (jnp.sum(rows) > bound).astype(jnp.int32)
        # the head over the last token's row only (tests ask for all)
        last = jnp.maximum(n_valid - 1, 0)
        ids, logits = self._greedy(
            params, h if with_logits else lax.dynamic_slice_in_dim(
                h, last, 1), with_logits)
        if with_logits:
            ids = lax.dynamic_slice_in_dim(ids, last, 1)
        out = {"ids": ids, "expert_rows": expert_rows, **kv,
               **self._state_out(latent, s_all, conv_all, index, rings)}
        if bounded:
            out["moe_whole"] = moe_whole
        if routed:
            out["routed_rows"] = sum(routed)
        if with_logits:
            out["logits"] = logits
        return out

    @staticmethod
    def _state_out(latent, s_all, conv_all, index, rings) -> dict:
        arrays = {"cache_latent": latent, "cache_state": s_all,
                  "cache_conv": conv_all, "cache_index": index,
                  "cache_window": rings}
        return {k: v for k, v in arrays.items() if v is not None}

    def _window(self) -> int:
        """Rows a sliding layer sees (a method so that a planted fault
        can widen it)."""
        return self.cfg.window

    def decode_step(self, params, state, block_tables, tok, pos, alive, *,
                    attention: str = "auto", with_logits: bool = False):
        """One token of every slot. ``tok`` [slots] the token each live
        slot feeds, ``pos`` [slots] its position (= the tokens before
        it), ``alive`` [slots], ``block_tables`` [slots, NB]. A live
        row's latent row is written at ``pos`` through its table before
        the attention reads it and its recurrent rows move one token; a
        row that is not alive (a free slot, or one whose prompt is still
        being chunked in) writes the null block and keeps its recurrent
        rows to the bit. ``attention``: ``"auto"`` (the Pallas kernel
        ``paged_latent_attn`` on a TPU where the shapes allow),
        ``"pallas"`` or ``"xla"``. Returns ``ids``
        [slots] (the greedy next token), the state and the routing
        numbers of :meth:`block_step`."""
        c = self.cfg
        s = tok.shape[0]
        latent = state.get("cache_latent")
        s_all, conv_all = state.get("cache_state"), state.get("cache_conv")
        index, rings = state.get("cache_index"), state.get("cache_window")
        kv = {k: state[k] for k in _KV_ARRAYS if k in state}
        n_mla, nb, bs, r = (kv["cache_k"] if kv else latent).shape
        flat = (n_mla * nb, bs, r)      # a layer's blocks, nb further on
        bt = jnp.asarray(block_tables, jnp.int32)
        live = jnp.asarray(alive) != 0
        pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, bt.shape[1] * bs - 1)
        pbid = jnp.where(live, bt[jnp.arange(s), pos // bs], 0)
        off = pos % bs
        h = self._embed(params, tok)
        expert_rows = jnp.zeros((), jnp.int32)
        fullest = jnp.zeros((), jnp.int32)
        kda_at, mla_at = c.state_rows()
        sparse_at, window_at = (c.rows_of("mla_sparse"),
                                c.rows_of("mla_window"))
        gfull_at, gwin_at = c.rows_of("gqa_full"), c.rows_of("gqa_window")
        dense_at = c.rows_of("mla_dense")
        routed = [] if c.expert_groups > 1 else None
        scale = (c.qk_nope_dim + c.qk_rope_dim) ** -0.5
        for i in range(c.layers):
            lp = params["layers"][str(i)]
            n = _rms(h, lp["attn_norm"], c.norm_eps)
            if i in gfull_at:
                j, ap = gfull_at[i], lp["attn"]
                with jax.named_scope("gqa_full"):
                    q, k, v = self._gqa_qkv(ap, n, pos, "gqa_full")
                    kv["cache_k"] = kv["cache_k"].at[j, pbid, off].set(k)
                    kv["cache_v"] = kv["cache_v"].at[j, pbid, off].set(v)
                    ctx = gqa_ops.paged_gqa_decode_attention(
                        q, kv["cache_k"].reshape(flat),
                        kv["cache_v"].reshape(flat),
                        block_tables=bt + j * nb, pos=pos, impl=attention)
                    h = h + self._typed_out(ap, n, self._heads_out(ctx))
            elif i in gwin_at:
                j, ap = gwin_at[i], lp["attn"]
                with jax.named_scope("gqa_window"):
                    q, k, v = self._gqa_qkv(ap, n, pos, "gqa_window")
                    at = pos % c.ring
                    mine = jnp.arange(s)
                    # a row that is not alive keeps its ring to the bit
                    for name, rows in (("cache_window_k", k),
                                       ("cache_window_v", v)):
                        kv[name] = kv[name].at[j, mine, at].set(jnp.where(
                            live[:, None], rows, kv[name][j, mine, at]))
                    ctx = gqa_ops.gqa_window_decode_attention(
                        q, kv["cache_window_k"][j], kv["cache_window_v"][j],
                        pos, window=self._window())
                    h = h + self._typed_out(ap, n, self._heads_out(ctx))
            elif i in sparse_at:
                j, mp = sparse_at[i], lp["mla"]
                g = c.geometry("mla_sparse")
                with jax.named_scope("mla_sparse"):
                    cq, q = self._typed_q(mp, n, pos, g)
                    latent = latent.at[j, pbid, off].set(
                        self._typed_latent(mp, n, pos, g))
                    qi, ki, wi = self._index_inputs(mp["index"], n, cq, pos)
                    index = index.at[j, pbid, off].set(ki)
                    at, chosen = self._select_rows(dsa_ops.step_scores(
                        qi, wi, index.reshape(n_mla * nb, bs, -1),
                        bt + j * nb, pos), c.index_topk)
                    q_abs, w_vb = self._absorb(mp, q, g)
                    ctx = mla_ops.mla_gathered_attention(
                        q_abs, latent.reshape(flat),
                        block_tables=bt + j * nb, positions=at,
                        chosen=chosen, rank=g.rank)
                    h = h + self._typed_out(mp, n, self._unabsorb(ctx, w_vb))
            elif i in window_at:
                j, mp = window_at[i], lp["mla"]
                g = c.geometry("mla_window")
                with jax.named_scope("mla_window"):
                    _, q = self._typed_q(mp, n, pos, g)
                    at = pos % c.ring
                    mine = jnp.arange(s)
                    # a row that is not alive keeps its ring to the bit
                    rings = rings.at[j, mine, at].set(jnp.where(
                        live[:, None], self._typed_latent(mp, n, pos, g),
                        rings[j, mine, at]))
                    q_abs, w_vb = self._absorb(mp, q, g)
                    ctx = mla_ops.mla_window_decode_attention(
                        q_abs, rings[j], pos, window=self._window(),
                        rank=g.rank)
                    h = h + self._typed_out(mp, n, self._unabsorb(ctx, w_vb))
            elif i in dense_at:
                j, mp = dense_at[i], lp["mla"]
                g = c.geometry("mla_dense")
                with jax.named_scope("mla_dense"):
                    _, q = self._typed_q(mp, n, pos, g)
                    before = latent
                    latent = latent.at[j, pbid, off].set(
                        self._typed_latent(mp, n, pos, g))
                    # absorbed: the query meets the latent rows themselves
                    q_abs, w_vb = self._absorb(mp, q, g)
                    _log_schedule(mla_ops.decode_schedule(
                        s, g.heads, g.rank, bs, bt.shape[1], attention))
                    ctx = mla_ops.mla_decode_attention(
                        q_abs, self._step_pool(before, latent).reshape(flat),
                        block_tables=bt + j * nb, last=pos, rank=g.rank,
                        impl=attention)
                    h = h + self._typed_out(mp, n, self._unabsorb(ctx, w_vb))
            elif i in kda_at:
                j, kp = kda_at[i], lp["kda"]
                with jax.named_scope("kda"):
                    xx = jnp.concatenate(
                        [conv_all[j], self._mm(n, kp["wqkv"])],
                        axis=1)                     # [S, K * 3 H d]
                    y = jnp.sum(
                        xx.reshape(s, c.conv_kernel, -1)
                        * kp["conv"].astype(jnp.float32)[None], axis=1)
                    conv_all = conv_all.at[j].set(jnp.where(
                        live[:, None], xx[:, c.conv_channels:],
                        conv_all[j]))
                    q, k, v, log_a, b = self._kda_inputs(kp, n, y)
                    a = jnp.where(live[:, None, None], jnp.exp(log_a), 1.0)
                    b = jnp.where(live[:, None], b, 0.0)
                    o, s_new = kda_ops.kda_step(s_all[j], q, k, v, a, b)
                    s_all = s_all.at[j].set(s_new)
                    h = h + self._kda_output(kp, n, o)
            else:
                j, mp = mla_at[i], lp["mla"]
                with jax.named_scope("mla"):
                    latent = latent.at[j, pbid, off].set(
                        self._mla_latent(mp, n))
                    q = self._mm(n, mp["wq"]).reshape(
                        s, c.heads, c.qk_nope_dim + c.qk_rope_dim) * scale
                    wkvb = mp["wkvb"].reshape(c.kv_lora_rank, c.heads, -1)
                    # W_kvb absorbed: the query meets the latent itself
                    q_lat = jnp.einsum(
                        "shd,chd->shc",
                        q[..., :c.qk_nope_dim].astype(self.dtype),
                        wkvb[..., :c.qk_nope_dim].astype(self.dtype),
                        preferred_element_type=jnp.float32)
                    q_abs = jnp.concatenate(
                        [q_lat, q[..., c.qk_nope_dim:],
                         jnp.zeros((s, c.heads,
                                    c.latent_row - c.latent_dim))], axis=-1)
                    ctx = mla_ops.mla_decode_attention(
                        q_abs.astype(self.dtype), latent.reshape(flat),
                        block_tables=bt + j * nb, last=pos,
                        rank=c.kv_lora_rank, impl=attention)
                    ctx = jnp.einsum(
                        "shc,chd->shd", ctx.astype(self.dtype),
                        wkvb[..., c.qk_nope_dim:].astype(self.dtype),
                        preferred_element_type=jnp.float32)
                    h = h + self._mm(ctx.reshape(s, -1), mp["wo"])
            h, rows = self._ffn_of(i, lp, h, routed, live)
            if rows is not None:
                expert_rows += jnp.sum(rows > 0).astype(jnp.int32)
                fullest = jnp.maximum(fullest, jnp.max(rows))
        ids, logits = self._greedy(params, h, with_logits)
        mean_load = s * c.experts_per_token / c.experts
        out = {"ids": ids, "expert_rows": expert_rows,
               "max_expert_load": fullest.astype(jnp.float32) / mean_load,
               **kv,
               **self._state_out(latent, s_all, conv_all, index, rings)}
        if routed:
            out["routed_rows"] = sum(routed)
        if with_logits:
            out["logits"] = logits
        return out


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


@register_model("kimi_linear")
def _make_kimi_linear(config: TrainConfig) -> BlockDecoder:
    model = _make(config, DecoderBlockConfig.kimi_linear_48b_a3b())
    model.name = "kimi_linear"
    return model


@register_model("kimi_linear_tiny")
def _make_kimi_linear_tiny(config: TrainConfig) -> BlockDecoder:
    model = _make(config, DecoderBlockConfig.kimi_linear_tiny())
    model.name = "kimi_linear"
    return model


@register_model("dots3_note")
def _make_dots3_note(config: TrainConfig) -> BlockDecoder:
    model = _make(config, DecoderBlockConfig.dots3_note_prev())
    model.name = "dots3_note"
    return model


@register_model("dots3_note_tiny")
def _make_dots3_note_tiny(config: TrainConfig) -> BlockDecoder:
    model = _make(config, DecoderBlockConfig.dots3_note_tiny())
    model.name = "dots3_note"
    return model


@register_model("laguna")
def _make_laguna(config: TrainConfig) -> BlockDecoder:
    model = _make(config, DecoderBlockConfig.laguna_s_2_1())
    model.name = "laguna"
    return model


@register_model("laguna_tiny")
def _make_laguna_tiny(config: TrainConfig) -> BlockDecoder:
    model = _make(config, DecoderBlockConfig.laguna_tiny())
    model.name = "laguna"
    return model


@register_model("axk1")
def _make_axk1(config: TrainConfig) -> BlockDecoder:
    model = _make(config, DecoderBlockConfig.a_x_k1())
    model.name = "axk1"
    return model


@register_model("axk1_tiny")
def _make_axk1_tiny(config: TrainConfig) -> BlockDecoder:
    model = _make(config, DecoderBlockConfig.axk1_tiny())
    model.name = "axk1"
    return model

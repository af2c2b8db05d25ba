"""Pipeline-parallel BERT: GPipe over the encoder stack.

The reference has no pipeline parallelism (SURVEY.md §2.5: PP absent,
not required for parity); round 3 delivered the mechanism on an MLP
(:mod:`.pipe_mlp`). This model applies it to the transformer family:
the L encoder layers live STACKED (one pytree with leading dim L),
sharded over the ``pipe`` mesh axis — each stage holds ``L/P``
consecutive layers — while the embedding front-end and MLM head stay
replicated outside the pipeline. Microbatches flow through the stages
via the shared :func:`~..parallel.pipeline.pipeline_spmd` ring
(``ppermute`` neighbor hops over ICI).

Correctness contract (asserted in tests/test_pipe_bert.py): bound to a
``pipe > 1`` mesh, outputs/loss/grads equal the unbound single-device
model — including dropout, because BOTH paths split the batch into
``microbatches`` and fold the per-(microbatch, layer) key the same way
(the pipeline hands each stage the microbatch index it is processing;
the sequential oracle maps over microbatches with the same indices).

Composes with data parallelism exactly like PipeMlp: on a
``{data, pipe}`` mesh each data shard runs its own P-stage pipeline and
XLA inserts the gradient all-reduce over ``data``.

Composes with tensor parallelism (PP×TP, the Megatron large-model combo)
on a ``{data, pipe, model}`` mesh using the *sequence-parallel* Megatron
layout (Korthikanti et al., "Reducing Activation Recomputation"): between
blocks the residual stream is sharded over ``model`` along the SEQUENCE
dim (layernorm is per-token, so seq-sharded LN is exact and no compute is
duplicated across TP peers); each block does
``all_gather(seq) → column-parallel QKV/FFN-in → row-parallel O/FFN-out →
reduce_scatter(seq)``. This is the formulation that keeps every
parameter's gradient correct under ``shard_map`` transposition: no
activation or parameter is used redundantly across ``model`` members, so
the implicit cross-``model`` psum of unmentioned-axis cotangents sums
genuinely partial contributions. The ``ppermute`` stage hop moves the
seq-shard each TP peer already holds — pipeline traffic shrinks by the
TP degree.
"""

from __future__ import annotations

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..config import TrainConfig
from ..ops.attention import multi_head_attention
from ..parallel.mesh import AxisNames
from ..parallel.pipeline import make_pipeline, sequential_blocks
from ..parallel.sharding import ShardingRules
from ..ops import nn
from ..utils.pytree import path_str as _path_str
from .base import cast_floating, register_model, resolve_dtype
from .bert import Bert, BertConfig, _make


def _row_dense_scatter(p, x, axis: str, *, dtype):
    """Row-parallel dense + reduce-scatter: ``x`` is ``[b, s, in/t]`` (this
    member's contraction shard), kernel ``[in/t, out]``; partial products
    are summed over ``model`` AND scattered along the sequence dim in one
    ``psum_scatter`` (the Megatron-SP output collective), returning
    ``[b, s/t, out]``. Bias is added once, after the reduction, on the
    seq-shard (so its gradient contributions stay partial per member)."""
    kernel, bias = p["kernel"], p["bias"]
    if dtype is not None:
        x = x.astype(dtype)
        kernel = kernel.astype(dtype)
    y = lax.dot_general(x, kernel, (((x.ndim - 1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    y = lax.psum_scatter(y, axis, scatter_dimension=1, tiled=True)
    if dtype is not None:
        y = y.astype(dtype)
    return y + bias.astype(y.dtype)


@dataclasses.dataclass
class PipeBertConfig(BertConfig):
    microbatches: int = 4       # GPipe M (per data shard)


class PipeBert(Bert):
    """BERT with the encoder stack stacked+pipelined over ``pipe``."""

    name = "pipe_bert"

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._pipe_mesh = None

    # ------------------------------------------------------------------
    def bind_mesh(self, mesh) -> None:
        if mesh is not None and mesh.shape[AxisNames.PIPE] > 1:
            if self.cfg.layers % mesh.shape[AxisNames.PIPE]:
                raise ValueError(
                    f"layers={self.cfg.layers} not divisible by pipe "
                    f"axis size {mesh.shape[AxisNames.PIPE]}")
            tp = mesh.shape[AxisNames.MODEL]
            if tp > 1:
                if self.cfg.heads % tp:
                    raise ValueError(
                        f"heads={self.cfg.heads} not divisible by model "
                        f"axis size {tp} (PP×TP shards attention by head)")
                if self.cfg.intermediate % tp:
                    raise ValueError(
                        f"intermediate={self.cfg.intermediate} not "
                        f"divisible by model axis size {tp}")
                if self.attention_fn is not None:
                    raise ValueError(
                        "attention_fn (ring attention / seq parallelism) "
                        "does not compose with PP×TP: the TP layer body "
                        "computes attention over its local heads with the "
                        "full sequence")
            self._pipe_mesh = mesh
        else:
            self._pipe_mesh = None

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array):
        flat = super().init(rng)
        c = self.cfg
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[flat.pop(f"layer_{i}") for i in range(c.layers)])
        flat["layers"] = stacked
        return flat

    # ------------------------------------------------------------------
    def _dropout_tp(self, rng, x_local, tp_axis: str):
        """Dropout on a seq-sharded ``[b, s/t, h]`` tensor that is
        POSITIONALLY identical to ``nn.dropout`` on the full ``[b, s, h]``
        tensor: every TP member draws the full mask from the shared key
        and slices its own seq chunk (mask generation is cheap replicated
        compute; the values stream stays sharded)."""
        t = lax.axis_size(tp_axis)
        m = lax.axis_index(tp_axis)
        b, sl, hd = x_local.shape
        keep = 1.0 - self.cfg.dropout
        full = jax.random.bernoulli(rng, keep, (b, sl * t, hd))
        shard = lax.dynamic_slice_in_dim(full, m * sl, sl, 1)
        return jnp.where(shard, x_local / keep, 0.0)

    def _layer_tp(self, lp, x, mask, lrng, *, train: bool,
                  use_dropout: bool, tp_axis: str):
        """One encoder layer in the Megatron sequence-parallel TP layout.

        ``x`` is the residual stream seq-sharded over ``model``
        (``[b, s/t, hidden]``); ``lp`` leaves are this member's kernel
        shards (QKV/FFN-in column-split, O/FFN-out row-split; LN params
        and row-dense biases full). Numerically equal to :meth:`_layer`
        up to reduction order (contractions split over ``model``)."""
        ap = lp["attn"]
        d_local = ap["q"]["kernel"].shape[-1]
        heads_local = d_local // self.head_dim

        h_full = lax.all_gather(x, tp_axis, axis=1, tiled=True)  # [b,s,h]
        b, s, _ = h_full.shape

        def split(y):
            return y.reshape(b, s, heads_local, self.head_dim)

        q = split(nn.dense(ap["q"], h_full, dtype=self.dtype))
        k = split(nn.dense(ap["k"], h_full, dtype=self.dtype))
        v = split(nn.dense(ap["v"], h_full, dtype=self.dtype))
        ctx = multi_head_attention(q, k, v, mask=mask[:, None, None, :],
                                   impl=self.attention_impl,
                                   flash_kwargs=self.attention_kwargs
                                   or None)
        ctx = ctx.reshape(b, s, d_local)
        a = _row_dense_scatter(ap["o"], ctx, tp_axis, dtype=self.dtype)
        if use_dropout:
            a = self._dropout_tp(jax.random.fold_in(lrng, 1), a, tp_axis)
        h1 = nn.layernorm(lp["attn_ln"], x + a.astype(x.dtype))
        g = lax.all_gather(h1, tp_axis, axis=1, tiled=True)
        f = nn.dense(lp["ffn"]["in"], g, dtype=self.dtype)
        f = jax.nn.gelu(f.astype(jnp.float32)).astype(self.dtype)
        f = _row_dense_scatter(lp["ffn"]["out"], f, tp_axis,
                               dtype=self.dtype)
        if use_dropout:
            f = self._dropout_tp(jax.random.fold_in(lrng, 2), f, tp_axis)
        return nn.layernorm(lp["ffn_ln"], h1 + f.astype(h1.dtype))

    def _stage_fn(self, *, offset_fn, train: bool, use_dropout: bool,
                  rng, tp_axis: str | None = None):
        """(local_stack, {h, mask}, mb_idx) -> same-structure pytree:
        applies this stage's layers in order. ``offset_fn(n_local)``
        yields the stage's first GLOBAL layer index — per-layer dropout
        keys fold (global layer, microbatch), so pipelined and
        sequential paths derive identical randomness. With ``tp_axis``
        the per-layer body is the sequence-parallel TP variant."""
        if tp_axis is None:
            base = functools.partial(self._layer, train=train,
                                     use_dropout=use_dropout)
        else:
            base = functools.partial(self._layer_tp, train=train,
                                     use_dropout=use_dropout,
                                     tp_axis=tp_axis)
        layer = self._maybe_remat(base)

        def stage(stack, x, mb_idx):
            n_local = jax.tree_util.tree_leaves(stack)[0].shape[0]
            offset = offset_fn(n_local)

            def body(h, xs):
                lp, j = xs
                lrng = None
                if use_dropout:
                    lrng = jax.random.fold_in(
                        jax.random.fold_in(rng, offset + j), mb_idx)
                return layer(lp, h, x["mask"], lrng), None

            h, _ = lax.scan(body, x["h"],
                            (stack, jnp.arange(n_local)))
            return {"h": h, "mask": x["mask"]}

        return stage

    def encode(self, params, batch, rng=None, train: bool = False):
        c = self.cfg
        h, mask, use_dropout = self._embed(params, batch, rng, train)
        x = {"h": h, "mask": mask}
        if self._pipe_mesh is not None:
            mesh = self._pipe_mesh
            tp = mesh.shape[AxisNames.MODEL]
            tp_axis = AxisNames.MODEL if tp > 1 else None
            if tp > 1 and h.shape[1] % tp:
                raise ValueError(
                    f"sequence length {h.shape[1]} not divisible by model "
                    f"axis size {tp} (activations are seq-sharded over TP)")
            stage = self._stage_fn(
                offset_fn=lambda n_local:
                    lax.axis_index(AxisNames.PIPE) * n_local,
                train=train, use_dropout=use_dropout, rng=rng,
                tp_axis=tp_axis)
            param_specs = x_specs = None
            if tp > 1:
                param_specs = self._stacked_specs(params["layers"])
                # residual stream seq-sharded over model between blocks
                # (Megatron-SP); mask stays full — attention masks keys
                # over the whole sequence
                x_specs = {"h": P(AxisNames.BATCH, AxisNames.MODEL),
                           "mask": P(AxisNames.BATCH)}
            piped = make_pipeline(mesh, stage,
                                  num_microbatches=c.microbatches,
                                  param_specs=param_specs,
                                  x_specs=x_specs)
            out = piped(params["layers"], x)
        else:
            stage = self._stage_fn(offset_fn=lambda n_local: 0,
                                   train=train, use_dropout=use_dropout,
                                   rng=rng)
            # dropout keys are per-microbatch: the oracle must split the
            # same way; without dropout one "microbatch" is exact and
            # cheapest
            m = c.microbatches if use_dropout else 1
            out = sequential_blocks(stage, params["layers"], x,
                                    num_microbatches=m)
        return out["h"]

    # ------------------------------------------------------------------
    #: (pattern, trailing spec) for the stacked encoder's TP layout —
    #: ONE source of truth for both the GSPMD placement rules
    #: (sharding_rules) and the shard_map in_specs (_stacked_specs).
    #: Patterns match the path below ``layers/``; the leading (stage)
    #: dim always carries ``pipe``.
    _TP_STACK = (
        (r"attn/(q|k|v)/kernel|ffn/in/kernel",
         (None, AxisNames.MODEL)),               # column-parallel
        (r"attn/(q|k|v)/bias|ffn/in/bias", (AxisNames.MODEL,)),
        (r"(attn/o|ffn/out)/kernel",
         (AxisNames.MODEL, None)),               # row-parallel
    )

    def _stacked_specs(self, stacked):
        """shard_map PartitionSpecs for the stacked encoder params under
        PP×TP: leading dim over pipe, kernel dims per ``_TP_STACK``
        (LN params and row-dense biases replicated over model)."""
        def spec(path, _):
            p = _path_str(path)
            for pattern, tail in self._TP_STACK:
                if re.search(pattern, p):
                    return P(AxisNames.PIPE, *tail)
            return P(AxisNames.PIPE)
        return jax.tree_util.tree_map_with_path(spec, stacked)

    def sharding_rules(self, mesh_shape) -> ShardingRules:
        """Stacked encoder sharded over pipe (stage placement); with a
        ``model`` axis > 1 the kernels additionally shard Megatron-style
        and the embedding/MLM head reuse Bert's TP rules. All four
        combinations of {pipe, model} > 1 are covered — on a pure-TP mesh
        (pipe=1) the stacked kernels still model-shard and GSPMD
        parallelizes the sequential path."""
        fsdp = getattr(mesh_shape, "fsdp", 1) if mesh_shape else 1
        pipe = getattr(mesh_shape, "pipe", 1) if mesh_shape else 1
        tp = getattr(mesh_shape, "model", 1) if mesh_shape else 1
        if pipe <= 1 and tp <= 1:
            return ShardingRules(fsdp_axis_size=fsdp)
        # \b, not ^: rule paths come prefixed (params/layers/... in a
        # TrainState) — an anchored rule silently never matches and the
        # stack would fall back to replicated placement. Each _TP_STACK
        # pattern is wrapped (?:...) so its alternation stays under the
        # \blayers/ anchor.
        lead = AxisNames.PIPE if pipe > 1 else None
        rules = []
        if tp > 1:
            rules += [(r"\blayers/(?:" + pattern + ")", P(lead, *tail))
                      for pattern, tail in self._TP_STACK]
            rules += list(self.TP_EMBED_RULES)
        if pipe > 1:
            rules.append((r"\blayers/", P(AxisNames.PIPE)))
        return ShardingRules(rules=rules, fsdp_axis_size=fsdp)


@register_model("pipe_bert")
def _make_pipe_bert(config: TrainConfig) -> PipeBert:
    cfg = PipeBertConfig()
    return _make(config, cfg, cls=PipeBert)


@register_model("pipe_bert_tiny")
def _make_pipe_bert_tiny(config: TrainConfig) -> PipeBert:
    t = BertConfig.tiny()
    cfg = PipeBertConfig(**dataclasses.asdict(t))
    cfg.layers = 4              # 2 stages x 2 layers on the test mesh
    return _make(config, cfg, config_vocab=False, cls=PipeBert)

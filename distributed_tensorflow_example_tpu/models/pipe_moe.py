"""EP × PP: Mixture-of-Experts encoder layers inside GPipe stages.

Neither exists in the reference (SURVEY.md §2.5 marks PP and EP absent);
this model closes the composition-matrix cell VERDICT r4 missing #4
named: the ``pipe`` and ``expert`` axes live in ONE program. Every
encoder layer is an MoE layer (homogeneous blocks are what make a
stacked pipeline SPMD-able — a dense/MoE alternation cannot stack), the
layer stack is sharded over ``pipe`` exactly like
:class:`~.pipe_bert.PipeBert`, and inside each stage tick the FFN runs
the SAME explicit expert-parallel dataflow as
:func:`~..ops.moe.moe_ffn_ep_body`: tokens sharded over ``expert``,
``lax.all_to_all`` token exchange, local expert compute, exchange back.

Gradient correctness under ``shard_map`` follows the PP×TP design rule
(pipe_bert.py module docstring): nothing is computed redundantly across
``expert`` members — the batch is sharded over ``(data, fsdp, expert)``
inside the pipeline, so attention runs on each member's own token shard
and the router routes each member's own tokens; every unmentioned-axis
cotangent psum therefore sums genuinely partial contributions.

Aux (load-balancing + router-z) losses ride the pipeline as extra
microbatch-shaped accumulator leaves in the activation pytree: each
stage adds its layers' aux for the microbatch it is processing, and the
final values are batch means. Two semantics notes that make the parity
tests precise (tests/test_pipe_moe.py):

- Routing DECISIONS are per token (grouping-independent), so at a
  capacity where nothing drops, outputs/loss/grads on the aux-free path
  match the sequential model tightly. The aux STATS are per-(microbatch
  group) and the lb formula is nonlinear in them, so aux values depend
  on which examples share a group — a layout-defined property (member-
  major across the expert shards). The aux oracle reorders the batch to
  form the same groups and then matches at 1e-5. Capacity caveat as in
  test_moe.py: the explicit path's capacity is per token shard, so
  parity asserts use a generous capacity_factor.
- Dropout masks are drawn per token shard (independent across expert
  members — operationally sound), so bit-parity with the unsharded
  oracle under dropout is a pipe-only property, as in PipeBert.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..config import TrainConfig
from ..ops import moe
from ..parallel.mesh import AxisNames
from ..parallel.pipeline import make_pipeline, sequential_blocks
from ..parallel.sharding import ShardingRules
from ..utils.pytree import path_str as _path_str
from .base import register_model
from .bert import BertConfig, _make
from .pipe_bert import PipeBert, PipeBertConfig


@dataclasses.dataclass
class PipeMoeBertConfig(PipeBertConfig):
    n_experts: int = 8
    top_k: int = 1
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    router_z_weight: float = 0.0

    @classmethod
    def tiny(cls) -> "PipeMoeBertConfig":
        t = BertConfig.tiny()
        cfg = cls(**dataclasses.asdict(t))
        cfg.layers = 4            # 2 stages x 2 layers on the test mesh
        cfg.n_experts = 4
        cfg.capacity_factor = 2.0
        return cfg


class PipeMoeBert(PipeBert):
    """Pipelined BERT whose every encoder FFN is an expert-parallel MoE."""

    name = "pipe_moe_bert"

    # ------------------------------------------------------------------
    def bind_mesh(self, mesh) -> None:
        if mesh is not None and mesh.shape[AxisNames.MODEL] > 1:
            raise ValueError(
                "pipe_moe_bert composes pipe x expert; a model axis > 1 "
                "(EP x TP x PP) is not supported — use moe_bert for "
                "EP x TP or pipe_bert for PP x TP")
        if mesh is not None and mesh.shape[AxisNames.EXPERT] > 1:
            ep = mesh.shape[AxisNames.EXPERT]
            if self.cfg.n_experts % ep:
                raise ValueError(
                    f"n_experts={self.cfg.n_experts} not divisible by "
                    f"expert axis size {ep}")
        super().bind_mesh(mesh)
        # the EP dataflow needs the mesh even when pipe == 1 (pure EP
        # under a pipeline-of-one); PipeBert only records pipe > 1 meshes
        if (mesh is not None and self._pipe_mesh is None
                and mesh.shape[AxisNames.EXPERT] > 1):
            self._pipe_mesh = mesh

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array):
        # Bert layer structure with the FFN swapped for MoE weights,
        # then stacked [L, ...] like PipeBert (homogeneous blocks)
        flat = super(PipeBert, self).init(rng)
        c = self.cfg
        for i in range(c.layers):
            lp = flat[f"layer_{i}"]
            del lp["ffn"]
            lp["moe"] = moe.moe_ffn_init(
                jax.random.fold_in(rng, 10_000 + i), c.n_experts,
                c.hidden, c.intermediate,
                param_dtype=self.param_dtype)
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[flat.pop(f"layer_{i}") for i in range(c.layers)])
        flat["layers"] = stacked
        return flat

    # ------------------------------------------------------------------
    def _moe_ffn_in_stage(self, lp_moe, h, ep_axis, stat_axes):
        """FFN body for one layer inside the pipeline shard_map: the
        explicit EP dataflow when the expert axis is real, the dense
        dispatch otherwise (pipe-only meshes and the sequential
        oracle)."""
        c = self.cfg
        if ep_axis is not None:
            return moe.moe_ffn_ep_body(
                lp_moe, h, n_experts=c.n_experts,
                n_ranks=lax.axis_size(ep_axis), top_k=c.top_k,
                capacity_factor=c.capacity_factor, dtype=self.dtype,
                axis_name=ep_axis, stat_axes=stat_axes)
        return moe.moe_ffn(lp_moe, h, n_experts=c.n_experts,
                           top_k=c.top_k,
                           capacity_factor=c.capacity_factor,
                           dtype=self.dtype)

    def _moe_stage_fn(self, *, offset_fn, train: bool, use_dropout: bool,
                      rng, ep_axis: str | None, stat_axes):
        """(local_stack, {h, mask, lb, z, dropped}, mb_idx) -> same
        structure: this stage's MoE layers in order, aux accumulated
        onto the microbatch-shaped leaves."""
        def one_layer(lp, h, mask, lrng):
            h = self._attn_block(lp, h, mask, lrng, train=train,
                                 use_dropout=use_dropout)
            f, aux = self._moe_ffn_in_stage(lp["moe"], h, ep_axis,
                                            stat_axes)
            h = self._ffn_block(lp, h, f, lrng, use_dropout=use_dropout)
            return h, aux

        layer = self._maybe_remat(one_layer)

        def stage(stack, x, mb_idx):
            n_local = jax.tree_util.tree_leaves(stack)[0].shape[0]
            offset = offset_fn(n_local)

            def body(carry, xs):
                h, lb, z, dropped = carry
                lp, j = xs
                lrng = None
                if use_dropout:
                    lrng = jax.random.fold_in(
                        jax.random.fold_in(rng, offset + j), mb_idx)
                h, aux = layer(lp, h, x["mask"], lrng)
                return (h, lb + aux["lb_loss"], z + aux["z_loss"],
                        dropped + aux["dropped_fraction"]), None

            (h, lb, z, dropped), _ = lax.scan(
                body, (x["h"], jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)),
                (stack, jnp.arange(n_local)))
            # aux rides the activation pytree: broadcast this stage's
            # contribution onto the per-example accumulator leaves (every
            # example of the microbatch carries the same value, so the
            # final batch mean is the per-microbatch mean)
            b = x["lb"].shape[0]
            return {"h": h, "mask": x["mask"],
                    "lb": x["lb"] + jnp.broadcast_to(lb, (b,)),
                    "z": x["z"] + jnp.broadcast_to(z, (b,)),
                    "dropped": x["dropped"]
                    + jnp.broadcast_to(dropped, (b,))}

        return stage

    # ------------------------------------------------------------------
    def encode_with_aux(self, params, batch, rng=None,
                        train: bool = False):
        c = self.cfg
        h, mask, use_dropout = self._embed(params, batch, rng, train)
        b = h.shape[0]
        zero = jnp.zeros((b,), jnp.float32)
        x = {"h": h, "mask": mask, "lb": zero, "z": zero, "dropped": zero}
        mesh = self._pipe_mesh
        if mesh is not None:
            ep = mesh.shape[AxisNames.EXPERT]
            ep_axis = AxisNames.EXPERT if ep > 1 else None
            batch_axes = tuple(AxisNames.BATCH) + (
                (AxisNames.EXPERT,) if ep > 1 else ())
            stat_axes = batch_axes
            stage = self._moe_stage_fn(
                offset_fn=lambda n_local:
                    lax.axis_index(AxisNames.PIPE) * n_local,
                train=train, use_dropout=use_dropout, rng=rng,
                ep_axis=ep_axis, stat_axes=stat_axes)
            piped = make_pipeline(
                mesh, stage, num_microbatches=c.microbatches,
                param_specs=self._stacked_specs(params["layers"]),
                x_specs=jax.tree_util.tree_map(
                    lambda _: P(batch_axes), x))
            out = piped(params["layers"], x)
        else:
            stage = self._moe_stage_fn(
                offset_fn=lambda n_local: 0, train=train,
                use_dropout=use_dropout, rng=rng, ep_axis=None,
                stat_axes=())
            # ALWAYS the pipeline's microbatch split: MoE routing
            # (capacity, stats) is per-microbatch, so unlike the dense
            # PipeBert the no-dropout oracle cannot collapse to m=1
            out = sequential_blocks(stage, params["layers"], x,
                                    num_microbatches=c.microbatches)
        n_layers = jnp.float32(c.layers)
        return out["h"], {
            "lb_loss": jnp.mean(out["lb"]),
            "z_loss": jnp.mean(out["z"]),
            # visibility: mean over layers (loss terms stay sums — each
            # router is its own regularization target, as in MoeBert)
            "dropped_fraction": jnp.mean(out["dropped"]) / n_layers,
        }

    def encode(self, params, batch, rng=None, train: bool = False):
        return self.encode_with_aux(params, batch, rng, train)[0]

    # ------------------------------------------------------------------
    def loss(self, params, extras, batch, rng):
        seq_out, aux = self.encode_with_aux(params, batch, rng,
                                            train=True)
        w = batch["masked_weights"].astype(jnp.float32)
        # the MLM head loss is Bert's shared implementation (full or
        # fused blockwise core per cfg.lm_loss_impl — ops/losses.py)
        mlm, acc = self._mlm_loss_and_acc(params, seq_out, batch, w)
        total = (mlm + self.cfg.aux_weight * aux["lb_loss"]
                 + self.cfg.router_z_weight * aux["z_loss"])
        metrics = {"mlm_accuracy": acc, "mlm_loss": mlm,
                   "aux_loss": aux["lb_loss"],
                   "router_z_loss": aux["z_loss"],
                   "dropped_token_fraction": aux["dropped_fraction"]}
        return total, (metrics, extras)

    # ------------------------------------------------------------------
    #: stacked-MoE placement: leading dim pipe, expert dim expert
    _EP_STACK = (
        (r"moe/w_(in|out)", (AxisNames.EXPERT, None, None)),
        (r"moe/b_(in|out)", (AxisNames.EXPERT, None)),
    )

    def _stacked_specs(self, stacked):
        """shard_map specs: pipe on the stage dim, expert on the expert
        dim of the MoE arrays, router/LN/attention replicated across
        expert (their COMPUTE is per-token-shard, never redundant)."""
        def spec(path, _):
            p = _path_str(path)
            for pattern, tail in self._EP_STACK:
                if re.search(pattern, p):
                    return P(AxisNames.PIPE, *tail)
            return P(AxisNames.PIPE)
        return jax.tree_util.tree_map_with_path(spec, stacked)

    def sharding_rules(self, mesh_shape) -> ShardingRules:
        fsdp = getattr(mesh_shape, "fsdp", 1) if mesh_shape else 1
        pipe = getattr(mesh_shape, "pipe", 1) if mesh_shape else 1
        ep = getattr(mesh_shape, "expert", 1) if mesh_shape else 1
        if pipe <= 1 and ep <= 1:
            return ShardingRules(fsdp_axis_size=fsdp)
        lead = AxisNames.PIPE if pipe > 1 else None
        rules = [(r"\blayers/(?:" + pattern + ")", P(lead, *tail))
                 for pattern, tail in self._EP_STACK]
        if pipe > 1:
            rules.append((r"\blayers/", P(AxisNames.PIPE)))
        return ShardingRules(rules=rules, fsdp_axis_size=fsdp)


def _apply_overrides(cfg: PipeMoeBertConfig,
                     config: TrainConfig) -> PipeMoeBertConfig:
    """The shared --moe_* CLI knobs, minus the two that do not apply
    here: every pipelined layer is MoE (homogeneous stacking), so
    --moe_every has no meaning, and router jitter is not wired into the
    pipelined path — both hard-error instead of silently ignoring."""
    if config.moe_experts is not None:
        if config.moe_experts < 1:
            raise ValueError(
                f"moe_experts={config.moe_experts} must be >= 1")
        cfg.n_experts = config.moe_experts
    if config.moe_top_k is not None:
        cfg.top_k = config.moe_top_k
    if not 1 <= cfg.top_k <= cfg.n_experts:
        raise ValueError(f"moe_top_k={cfg.top_k} must be in "
                         f"[1, n_experts={cfg.n_experts}]")
    if config.moe_capacity_factor is not None:
        if config.moe_capacity_factor <= 0:
            raise ValueError("moe_capacity_factor must be > 0")
        cfg.capacity_factor = config.moe_capacity_factor
    if config.moe_aux_weight is not None:
        cfg.aux_weight = config.moe_aux_weight
    if config.moe_router_z_weight is not None:
        cfg.router_z_weight = config.moe_router_z_weight
    if config.moe_every is not None:
        raise ValueError(
            "moe_every does not apply to pipe_moe_bert: every pipelined "
            "layer is MoE (homogeneous blocks stack over pipe)")
    if config.moe_jitter is not None:
        raise ValueError(
            "moe_jitter is not wired into the pipelined MoE path — use "
            "moe_bert for jittered routing")
    return cfg


@register_model("pipe_moe_bert")
def _make_pipe_moe_bert(config: TrainConfig) -> PipeMoeBert:
    cfg = _apply_overrides(PipeMoeBertConfig(), config)
    return _make(config, cfg, cls=PipeMoeBert)


@register_model("pipe_moe_bert_tiny")
def _make_pipe_moe_bert_tiny(config: TrainConfig) -> PipeMoeBert:
    cfg = _apply_overrides(PipeMoeBertConfig.tiny(), config)
    return _make(config, cfg, config_vocab=False, cls=PipeMoeBert)

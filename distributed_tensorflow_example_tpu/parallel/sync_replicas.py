"""Sync data-parallel training: the ``SyncReplicasOptimizer`` replacement.

The reference's sync protocol (sync_replicas_optimizer.py:41-135 in the
reference stack; SURVEY.md §2.2, §3.3) was: every worker pushes gradients
into per-variable ConditionalAccumulators on the PS, a chief queue-runner
thread takes ``replicas_to_aggregate`` gradients, **averages** them, applies
the update, bumps ``global_step``, and enqueues tokens that each blocked
worker dequeues as its barrier. Stale gradients are dropped by a
``local_step`` check.

On TPU that entire protocol — accumulate, average, apply, barrier — is a
single compiled program: gradients are averaged by one fused XLA all-reduce
over ICI, the update is computed identically on every chip, and the
"barrier" is simply that the collective cannot complete until every replica
arrives. Staleness is impossible (SPMD lockstep), so the ``local_step``
machinery has no analogue; backup replicas (``total_num_replicas >
replicas_to_aggregate``) don't exist because ICI topology is fixed —
documented as intentionally dropped (SURVEY.md §2.5).

Two implementations are provided:

- ``mode="auto"`` (default, fastest): placement-driven. Params are laid out
  by :mod:`.sharding` rules (replicated or fsdp), the batch is split over
  the batch axes, and ``jax.jit`` inserts the collectives. This is the
  idiomatic form and supports every mesh axis (tp/sp/... come from the
  model's own sharding rules). Normalization statistics taken over the
  batch dimension become *global*-batch statistics automatically (sync-BN
  semantics for free).
- ``mode="shard_map"``: explicit per-replica SPMD with a hand-written
  ``pmean`` — the literal accumulate/average/apply dataflow, useful for
  pedagogy and for asserting the auto path's semantics in tests.
  CAVEAT: batch statistics computed inside the loss (BatchNorm) are
  per-replica here (local-batch mean/var in the forward pass; running
  stats pmean'd afterwards), while ``mode="auto"`` yields global-batch
  sync-BN statistics. The auto==shard_map equivalence therefore holds for
  models without cross-batch statistics (MLP/transformers); BN models are
  excluded from the claim (matches the reference, whose per-worker
  towers also normalized with local-batch statistics).

``accum_steps > 1`` adds microbatch gradient accumulation via ``lax.scan``
(accumulate-N-then-apply *within* a replica — the TPU-meaningful residue of
the PS-side accumulate-N protocol).

The canonical loss signature framework-wide::

    loss_fn(params, extras, batch, rng) -> (loss, (aux_metrics, new_extras))

where ``extras`` is non-trained model state (BatchNorm stats etc.; ``{}``
when unused) and ``aux_metrics`` is a dict of scalars.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import SyncConfig
from ..train.state import TrainState
from .mesh import AxisNames, batch_axis_size
from .sharding import ShardingRules, batch_pspec, state_shardings

# loss_fn(params, extras, batch, rng) -> (loss, (aux_metrics, new_extras))
LossFn = Callable[[Any, Any, Any, jax.Array], tuple[jax.Array, tuple[dict, Any]]]


def _split_microbatches(batch: Any, accum_steps: int) -> Any:
    """[B, ...] -> [accum, B/accum, ...] on every leaf."""
    def r(x):
        b = x.shape[0]
        if b % accum_steps:
            raise ValueError(
                f"batch dim {b} not divisible by accum_steps={accum_steps}")
        return x.reshape((accum_steps, b // accum_steps) + x.shape[1:])
    return jax.tree_util.tree_map(r, batch)


def _grads_and_metrics(loss_fn: LossFn, params, extras, batch, rng,
                       accum_steps: int):
    """Gradients (+ loss/aux/extras) with optional microbatch accumulation."""
    vg = jax.value_and_grad(loss_fn, has_aux=True)
    if accum_steps <= 1:
        (loss, (aux, new_extras)), grads = vg(params, extras, batch, rng)
        return grads, loss, aux, new_extras

    micro = _split_microbatches(batch, accum_steps)

    def body(carry, inp):
        i, mb = inp
        gsum, lsum, ex = carry
        # distinct rng per microbatch: otherwise dropout masks repeat and
        # accumulation no longer approximates the full-batch step
        (l, (aux, ex)), g = vg(params, ex, mb, jax.random.fold_in(rng, i))
        gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
        return (gsum, lsum + l, ex), aux

    zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
    (gsum, lsum, new_extras), auxes = lax.scan(
        body, (zero_g, jnp.zeros(()), extras),
        (jnp.arange(accum_steps), micro))
    grads = jax.tree_util.tree_map(lambda g: g / accum_steps, gsum)
    # average aux over microbatches so metrics describe the whole batch,
    # consistent with the loss
    aux = jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), auxes)
    return grads, lsum / accum_steps, aux, new_extras


class SyncReplicas:
    """Builds the compiled sync train step for a (loss_fn, optimizer, mesh).

    Usage::

        sync = SyncReplicas(loss_fn, tx, mesh)
        state = sync.init(model_init, seed=0)
        state, metrics = sync.step(state, sync.shard_batch(batch))
    """

    def __init__(self,
                 loss_fn: LossFn,
                 tx: optax.GradientTransformation,
                 mesh: Mesh,
                 *,
                 sync: SyncConfig | None = None,
                 rules: ShardingRules | None = None,
                 donate: bool = True,
                 debug_checks: bool = False,
                 anomaly_policy: str = "halt"):
        self.loss_fn = loss_fn
        self.tx = tx
        self.mesh = mesh
        self.sync = sync or SyncConfig()
        if anomaly_policy not in ("halt", "skip", "rollback"):
            raise ValueError(
                f"anomaly_policy must be halt|skip|rollback, got "
                f"{anomaly_policy!r}")
        # on-device anomaly handling (no per-step host sync): every policy
        # guards the update — a step whose loss or global grad-norm is
        # non-finite applies the IDENTITY update (params, optimizer state,
        # extras, and the step rng all keep their previous values; only
        # the step counter and anomaly_count advance), so non-finite
        # numbers can never enter the training state. Under skip/rollback
        # the step's metrics are additionally sanitized to a -1.0
        # sentinel (the update never happened; publishing its NaN loss
        # would poison the metric stream the policies promise to keep
        # finite). Under halt the raw values are published — they are
        # the debugging evidence, and NanHook keys off them.
        self.anomaly_policy = anomaly_policy
        self.rules = rules or ShardingRules(
            fsdp_axis_size=mesh.shape[AxisNames.FSDP])
        self.num_replicas = batch_axis_size(mesh)
        self.last_cost_analysis: dict | None = None   # set by precompile()
        self.last_compile_seconds: float | None = None     # likewise
        if (self.sync.replicas_to_aggregate is not None
                and self.sync.replicas_to_aggregate != self.num_replicas):
            raise ValueError(
                "replicas_to_aggregate must equal the batch-axis size "
                f"({self.num_replicas}) on TPU: partial aggregation has no "
                "SPMD analogue (reference backup-replica semantics dropped, "
                "see module docstring)")
        if (self.sync.total_num_replicas is not None
                and self.sync.total_num_replicas != self.num_replicas):
            raise ValueError(
                "total_num_replicas != replicas_to_aggregate (backup "
                f"replicas; got {self.sync.total_num_replicas} vs "
                f"{self.num_replicas}) has no TPU analogue: ICI topology "
                "is fixed, so spare replicas cannot exist (reference "
                "backup-replica semantics dropped, see module docstring)")
        if self.sync.mode not in ("auto", "shard_map"):
            raise ValueError(f"unknown sync mode {self.sync.mode!r}")

        donate_args = (0,) if donate else ()
        step_fn = (self._auto_step if self.sync.mode == "auto"
                   else self._shard_map_step)
        if debug_checks:
            # SURVEY.md §5.2: checkify-instrumented step — every NaN/Inf
            # produced *inside* the compiled program (not just in the final
            # loss, as NanHook sees) is caught at the step where it occurs,
            # with the op's source location. Debug-only: adds a host sync
            # and error plumbing per step; no donation (checkify rewrites
            # the jaxpr and aliasing is not worth fighting here).
            from jax.experimental import checkify
            # deliberately un-donated (see docstring above): checkify
            # rewrites the jaxpr and buffer aliasing is not worth
            # fighting on a debug-only path
            checked = jax.jit(checkify.checkify(       # graftlint: disable=DON01
                step_fn, errors=checkify.float_checks))
            checked_multi = jax.jit(checkify.checkify(  # graftlint: disable=DON01
                self._multi_step, errors=checkify.float_checks))

            def step_with_checks(state, batch):
                err, out = checked(state, batch)
                checkify.check_error(err)
                return out

            def multi_step_with_checks(state, stacked):
                err, out = checked_multi(state, stacked)
                checkify.check_error(err)
                return out

            self.step = step_with_checks
            self.multi_step = multi_step_with_checks
            return
        if self.sync.mode == "auto":
            self.step = jax.jit(self._auto_step, donate_argnums=donate_args)
        else:
            self.step = jax.jit(self._shard_map_step,
                                donate_argnums=donate_args)
        self.multi_step = jax.jit(self._multi_step,
                                  donate_argnums=donate_args)

    # ---- AOT compile / cost analysis ------------------------------------
    def precompile(self, state: TrainState, batch, *,
                   multi: bool = False) -> dict:
        """AOT-compile the (multi_)step for these arguments' avals, swap the
        dispatch path to the compiled executable, and return XLA's cost
        analysis (flops / bytes accessed / ...) for it.

        This is what makes ``--step_timing`` records meaningful: the
        executable is fixed, its static cost is recorded once, and
        subsequent per-dispatch wall times measure exactly that program
        (WorkerCacheLogger parity, SURVEY.md §2.4/§5.1). No-op (returns {})
        under ``debug_checks``: checkify wraps the step in host-side error
        plumbing that is not a single executable."""
        name = "multi_step" if multi else "step"
        fn = getattr(self, name)
        if not hasattr(fn, "lower"):        # checkify wrapper: no AOT path
            return {}
        lowered = fn.lower(state, batch)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        # set-up time, reported apart from the step times: the XLA
        # compile alone (tracing is not in it), so a warm persistent
        # compilation cache shows here as a short compile
        self.last_compile_seconds = time.perf_counter() - t0
        setattr(self, name, compiled)
        raw = compiled.cost_analysis() or {}
        if isinstance(raw, (list, tuple)):  # older jax: one dict per device
            raw = raw[0] if raw else {}
        self.last_cost_analysis = {
            k: float(v) for k, v in raw.items()
            if k in ("flops", "optimal_seconds", "transcendentals",
                     "bytes accessed")}
        return self.last_cost_analysis

    # ---- state / batch placement ---------------------------------------
    def init(self,
             init_fn: Callable[[jax.Array], Any],
             *, seed: int = 0, prng_impl: str | None = None) -> TrainState:
        """Initialize a sharded TrainState directly on the mesh.

        ``init_fn(rng)`` returns either ``params`` or ``(params, extras)``.

        The chief-initializes-then-workers-wait protocol of the reference
        (SessionManager.prepare_session / wait_for_session, SURVEY.md §3.2)
        is unnecessary under SPMD: every process runs the same seeded init
        program, so all replicas start bit-identical by construction.

        ``prng_impl`` selects the key implementation ("threefry2x32"
        default; "rbg" uses the TPU's native RNG — measured 23 ms/step
        faster on BERT-base, dropout-mask generation dominates threefry's
        cost on TPU). The impl sticks to the key through split/fold_in,
        so the whole training stream follows it.
        """
        rng = jax.random.key(seed, impl=prng_impl)   # None = jax default
        init_rng, state_rng = jax.random.split(rng)

        def build():
            out = init_fn(init_rng)
            params, extras = out if isinstance(out, tuple) else (out, {})
            return TrainState.create(params=params, tx=self.tx,
                                     extras=extras, rng=state_rng)

        abstract = jax.eval_shape(build)
        shardings = state_shardings(self.mesh, abstract, self.rules)
        return jax.jit(build, out_shardings=shardings)()

    def shard_batch(self, batch: Any) -> Any:
        from .sharding import shard_batch
        return shard_batch(self.mesh, batch)

    def shard_stacked_batch(self, stacked: Any) -> Any:
        """Place a [K, B, ...] stack of K batches for :meth:`multi_step`:
        dim 0 is the loop axis (unsharded), dim 1 the batch split."""
        sh = NamedSharding(self.mesh, batch_pspec(leading_extra=1))
        if jax.process_count() > 1:
            return jax.tree_util.tree_map(
                lambda x: jax.make_array_from_process_local_data(sh, x),
                stacked)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), stacked)

    # ---- step implementations ------------------------------------------
    def _update(self, state: TrainState, grads, loss, aux, new_extras):
        updates, opt_state = self.tx.update(grads, state.opt_state,
                                            state.params)
        params = optax.apply_updates(state.params, updates)
        grad_norm = optax.global_norm(grads)
        # on-device finite-check of loss and global grad-norm, folded into
        # the compiled step (NanHook's per-step host sync is the debug
        # fallback). For a finite step the cond takes the computed branch
        # unchanged, so a healthy run's state and metric stream stay
        # BIT-IDENTICAL to the unguarded update.
        finite = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
        params, opt_state, extras, rng = lax.cond(
            finite,
            lambda: (params, opt_state, new_extras,
                     jax.random.fold_in(state.rng, state.step)),
            # identity update: optimizer state and the step rng untouched
            # for the anomalous batch
            lambda: (state.params, state.opt_state, state.extras,
                     state.rng))
        anomaly_count = state.anomaly_count + (
            1 - finite.astype(jnp.int32))
        new_state = state.replace(
            step=state.step + 1, params=params, opt_state=opt_state,
            extras=extras, rng=rng, anomaly_count=anomaly_count)
        metrics = {"loss": loss, "grad_norm": grad_norm, **aux}
        if self.anomaly_policy in ("skip", "rollback"):
            # the update was skipped: publish the -1.0 skipped sentinel
            # (the token_accuracy_every_n convention) instead of values
            # that never reached the state
            metrics = jax.tree_util.tree_map(
                lambda v: jnp.where(finite, v, -jnp.ones_like(v)), metrics)
        metrics["anomaly_count"] = anomaly_count
        return new_state, metrics

    def _auto_step(self, state: TrainState, batch):
        """Placement-driven: XLA inserts the gradient all-reduce because the
        loss is a mean over the (data-sharded) global batch while params are
        replicated/fsdp-sharded. One fused program = SURVEY.md §3.3 steps
        1-4 plus the chief aggregation loop."""
        rng = jax.random.fold_in(state.rng, state.step)
        # the mesh is the trace's ambient one: what jit cannot partition
        # by itself (a Mosaic kernel) shard_maps itself over it
        with jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh):
            grads, loss, aux, new_extras = _grads_and_metrics(
                self.loss_fn, state.params, state.extras, batch, rng,
                self.sync.accum_steps)
        return self._update(state, grads, loss, aux, new_extras)

    def _shard_map_step(self, state: TrainState, batch):
        """Explicit SPMD: per-replica grads then hand-written pmean — the
        literal accumulate→average→apply→barrier dataflow. Params must be
        replicated (fsdp/tp rules are the auto path's job)."""
        axes = AxisNames.BATCH

        @partial(jax.shard_map, mesh=self.mesh,
                 in_specs=(P(), jax.tree_util.tree_map(
                     lambda _: batch_pspec(), batch)),
                 out_specs=P(),
                 check_vma=False)
        def run(st: TrainState, local_batch):
            rng = jax.random.fold_in(st.rng, st.step)
            grads, loss, aux, new_extras = _grads_and_metrics(
                self.loss_fn, st.params, st.extras, local_batch, rng,
                self.sync.accum_steps)
            # the all-reduce: average of per-replica gradient means
            grads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, axes), grads)
            loss = lax.pmean(loss, axes)
            aux = jax.tree_util.tree_map(lambda a: lax.pmean(a, axes), aux)
            new_extras = jax.tree_util.tree_map(
                lambda e: lax.pmean(e, axes), new_extras)
            return self._update(st, grads, loss, aux, new_extras)

        return run(state, batch)

    def _multi_step(self, state: TrainState, stacked_batches):
        """K training steps in ONE device dispatch (``lax.scan`` over a
        [K, B, ...] batch stack) — the analogue of the TPU-era
        ``iterations_per_loop`` host→device loop: per-step host dispatch
        (a real cost on latency-y links) is paid once per K steps.
        Returns the state after K steps and the LAST step's metrics."""
        step_fn = (self._auto_step if self.sync.mode == "auto"
                   else self._shard_map_step)
        state, metrics = lax.scan(step_fn, state, stacked_batches)
        return state, jax.tree_util.tree_map(lambda a: a[-1], metrics)


def make_sync_train_step(loss_fn: LossFn,
                         tx: optax.GradientTransformation,
                         mesh: Mesh,
                         **kwargs) -> SyncReplicas:
    """Functional alias for ``SyncReplicas(...)`` mirroring the reference's
    ``opt = SyncReplicasOptimizer(base_opt, ...); train_op = opt.minimize``
    construction site (SURVEY.md §3.2)."""
    return SyncReplicas(loss_fn, tx, mesh, **kwargs)

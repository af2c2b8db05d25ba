"""Trainer: the Supervisor / MonitoredTrainingSession replacement.

The reference's bring-up (SURVEY.md §3.2) was: chief restores-or-inits and
starts summary/checkpoint/step-counter threads; workers poll until the
session is ready; then everyone loops ``sess.run(train_op)``. Under SPMD
there is no session to wait for — every process deterministically builds the
same state (or restores the same checkpoint) and runs the same compiled
step — so the Trainer is a plain loop plus the hook machinery:

- restore-or-init          → :func:`~..ckpt.checkpoint.restore_or_init`
  (prepare_session parity)
- Supervisor threads       → hooks (chief-side effects only)
- Coordinator should_stop  → hooks returning True / StopAtStepHook
- per-step feed_dict       → ShardedLoader batches placed with NamedSharding

Perf note: the loop is async-dispatch — device metrics are only pulled to
host on steps where some hook asks (``wants_metrics``), so steady-state
steps queue back-to-back on device with no host round-trip.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Any, Iterator

import jax
import numpy as np

from ..ckpt.checkpoint import CheckpointManager, restore_or_init
from ..config import TrainConfig, anomaly_settings
from ..data.loader import make_loader
from ..obs import trace as obs_trace
from ..obs.registry import Registry
from ..obs.trace import add_span, span
from ..parallel.mesh import batch_axis_size, build_mesh
from ..parallel.sync_replicas import SyncReplicas
from ..runtime import faults
from ..utils.logging import get_logger
from ..utils.metrics import MetricsLogger
from . import hooks as hooks_lib
from .optimizers import find_ema_params, make_optimizer, make_schedule
from .state import TrainState, param_count

log = get_logger("trainer")


def _host_metric(v):
    """Device metric -> JSON-ready host value: scalars become floats,
    vectors (MoE per-expert load) become lists — the JSONL sink takes
    them; scalar hooks skip them."""
    return float(v) if np.ndim(v) == 0 else np.asarray(v).tolist()


class Trainer:
    """End-to-end training driver for a registered model.

    Args:
      model: Model-protocol object.
      config: TrainConfig.
      train_arrays/eval_arrays: batch-keyed numpy arrays (e.g. {"x","y"}).
      mesh: optional prebuilt Mesh (default: from config.mesh over all
        devices).
      hooks: extra hooks appended after the default set.
      process_index/num_processes: data-sharding coordinates (default: from
        the JAX runtime).
    """

    def __init__(self, model, config: TrainConfig,
                 train_arrays: dict[str, np.ndarray],
                 eval_arrays: dict[str, np.ndarray] | None = None,
                 *, mesh=None, hooks: list[hooks_lib.Hook] | None = None,
                 process_index: int | None = None,
                 num_processes: int | None = None,
                 train_transform=None):
        self.model = model
        self.config = config
        self.mesh = mesh if mesh is not None else build_mesh(config.mesh)
        self.train_arrays = train_arrays
        self.eval_arrays = eval_arrays
        # per-batch augmentation hook (ShardedLoader transform contract:
        # randomness keyed on (seed, epoch, global index) only)
        self.train_transform = train_transform

        if hasattr(model, "bind_mesh"):
            # mesh-aware models (pipeline stages; mirrors how ring
            # attention binds a mesh via attention_fn)
            model.bind_mesh(self.mesh)
        self.tx = make_optimizer(config.optimizer)
        self._schedule = make_schedule(config.optimizer)
        rules = model.sharding_rules(config.mesh)
        # self-healing config (validated before any trace): the anomaly
        # policy shapes the compiled step (identity update + metric
        # sanitization) and the policy hook; the fault spec arms the
        # injection seams process-wide (inert when empty)
        anomaly_settings(config)
        self._rollback_pending = False
        self._rollback_before: int | None = None
        self._faults_installed = False
        if config.fault_spec:
            faults.install(faults.parse_spec(config.fault_spec,
                                             seed=config.seed))
            self._faults_installed = True
        self.sync = SyncReplicas(model.loss, self.tx, self.mesh,
                                 sync=config.sync, rules=rules,
                                 debug_checks=config.obs.debug_checks,
                                 anomaly_policy=config.on_anomaly)

        # telemetry registry (obs/registry.py). The trainer serves no
        # /metrics page, so it keeps only what something reads: the
        # data-wait histogram (the benchmark's train_data_wait_ms, a
        # snapshot at each edge of its window). Steps, saves, rollbacks
        # and anomalies are in the JSONL log and on the trace lanes.
        self.registry = Registry(namespace="training")
        self._h_data_wait = self.registry.histogram(
            "train_data_wait_seconds",
            "host time blocked on the data loader per dispatch")

        self.ckpt_manager = (
            CheckpointManager(config.checkpoint.directory,
                              max_to_keep=config.checkpoint.max_to_keep,
                              keep_every_n_hours=(
                                  config.checkpoint.keep_checkpoint_every_n_hours),
                              async_save=config.checkpoint.async_save,
                              sharded=config.checkpoint.sharded)
            if config.checkpoint.directory else None)
        self.metrics_logger = MetricsLogger(config.obs.metrics_path,
                                            tb_logdir=config.obs.tb_logdir)

        self.process_index = (jax.process_index() if process_index is None
                              else process_index)
        self.num_processes = (jax.process_count() if num_processes is None
                              else num_processes)

        self.state: TrainState | None = None
        self.start_step = 0
        self.hooks = self._default_hooks() + list(hooks or [])
        self._eval_fn = None

        if config.early_stop_metric:
            if self.eval_arrays is None or not config.eval_every_steps:
                raise ValueError(
                    "early_stop_metric needs eval data AND "
                    "eval_every_steps > 0 (improvement is judged at the "
                    "eval cadence)")
            if config.early_stop_mode not in ("max", "min"):
                raise ValueError("early_stop_mode must be max|min, got "
                                 f"{config.early_stop_mode!r}")
            if config.early_stop_patience < 1:
                raise ValueError("early_stop_patience must be >= 1")
        self._early_best: float | None = None
        self._early_misses = 0
        self._last_eval: tuple[int, dict] | None = None

        if config.checkpoint.keep_best_metric and (
                self.eval_arrays is None or self.ckpt_manager is None):
            # fail fast: best tracking without an eval split OR without
            # a checkpoint directory would be a silent no-op (both
            # save_best call sites are eval-gated and manager-gated)
            raise ValueError(
                "keep_best_metric needs eval data and a checkpoint "
                "directory (missing: "
                + ("eval data" if self.eval_arrays is None
                   else "checkpoint.directory") + ")")

        k = config.steps_per_loop
        if k > 1:
            # hooks fire on step % cadence == 0; a K-step jump only lands on
            # those boundaries when the cadence divides by K (the same
            # discipline TPU-era iterations_per_loop imposed)
            for name, every in (("log_every_steps", config.obs.log_every_steps),
                                ("summary_every_steps",
                                 config.obs.summary_every_steps),
                                ("param_histograms_every_steps",
                                 config.obs.param_histograms_every_steps),
                                ("save_steps", config.checkpoint.save_steps),
                                ("eval_every_steps", config.eval_every_steps)):
                if every and every % k:
                    raise ValueError(
                        f"{name}={every} must be a multiple of "
                        f"steps_per_loop={k} (hooks only observe loop "
                        "boundaries)")

    # ------------------------------------------------------------------
    def _default_hooks(self) -> list[hooks_lib.Hook]:
        """The hook set MonitoredTrainingSession wires for a chief
        (monitored_session.py:428-609 parity, SURVEY.md §2.2)."""
        cfg = self.config
        hs: list[hooks_lib.Hook] = [
            hooks_lib.StopAtStepHook(cfg.train_steps),
            hooks_lib.LoggingHook(cfg.obs.log_every_steps),
            hooks_lib.StepCounterHook(cfg.obs.log_every_steps,
                                      batch_size=cfg.data.batch_size,
                                      metrics_logger=self.metrics_logger),
        ]
        # anomaly policy driver: rides the log cadence so it adds NO
        # metric materializations a default run doesn't already pay.
        # With logging tuned OFF (log_every_steps=0): under the default
        # 'halt' policy the hook is omitted entirely — the on-device
        # identity update still protects the state, and a run that
        # disabled host syncs keeps zero of them; an EXPLICIT
        # skip/rollback policy is a request for active healing, so it
        # gets a 100-step fallback cadence (rounded to a loop boundary)
        spl = max(1, cfg.steps_per_loop)
        every = cfg.obs.log_every_steps
        if not every and cfg.on_anomaly != "halt":
            every = ((100 + spl - 1) // spl) * spl
        if every:
            hs.append(hooks_lib.AnomalyPolicyHook(
                cfg.on_anomaly, cfg.max_anomalies, every_steps=every))
        if cfg.obs.summary_every_steps:
            hs.append(hooks_lib.SummaryHook(self.metrics_logger,
                                            cfg.obs.summary_every_steps))
        if cfg.obs.param_histograms_every_steps:
            hs.append(hooks_lib.ParamHistogramHook(
                self.metrics_logger,
                cfg.obs.param_histograms_every_steps))
        if cfg.obs.check_nans:
            hs.append(hooks_lib.NanHook())
        if cfg.obs.step_timing:
            hs.append(hooks_lib.StepTimingHook(self.metrics_logger,
                                               cfg.obs.log_every_steps))
        if self.ckpt_manager and (cfg.checkpoint.save_steps
                                  or cfg.checkpoint.save_secs):
            hs.append(hooks_lib.CheckpointSaverHook(
                self.ckpt_manager, save_steps=cfg.checkpoint.save_steps,
                save_secs=cfg.checkpoint.save_secs))
            # SIGTERM → save-and-exit; multi-host runs coordinate the
            # stop step through the TSL preemption sync point (see
            # PreemptionHook docstring)
            hs.append(hooks_lib.PreemptionHook())
        if cfg.obs.profile_steps and cfg.obs.profile_dir:
            hs.append(hooks_lib.ProfilerHook(cfg.obs.profile_dir,
                                             *cfg.obs.profile_steps))
        return hs

    # ------------------------------------------------------------------
    def learning_rate_at(self, step: int) -> float:
        """The LR applied by the update that PRODUCED completed step
        ``step`` (optax evaluates the schedule at the pre-increment
        count, i.e. ``sched(step - 1)``) — so a metrics record at step N
        correlates with the LR that actually scaled step N's gradients.
        Logged next to steps/sec like the reference era's learning_rate
        summary."""
        return float(self._schedule(max(0, step - 1)))

    # ------------------------------------------------------------------
    def initialize(self) -> TrainState:
        """Restore-or-init (SessionManager.prepare_session parity)."""
        state, restored = restore_or_init(
            self.ckpt_manager,
            lambda: self.sync.init(self.model.init, seed=self.config.seed,
                                   prng_impl=self.config.prng_impl))
        self.state = state
        self.start_step = int(jax.device_get(state.step))
        if restored:
            log.info("restored checkpoint at step %d", self.start_step)
            if self.config.early_stop_metric:
                self._early_stop_load()   # patience survives preemption
        else:
            log.info("initialized fresh state: %d params",
                     param_count(state.params))
            if self.config.checkpoint.warm_start:
                # init_from_checkpoint parity: params only, on a fresh
                # init — a checkpoint in OUR directory means resume, and
                # resume always wins over warm start
                from ..ckpt.warm_start import (parse_assignment_map,
                                               warm_start)
                from .optimizers import reset_ema
                params, report = warm_start(
                    state.params, self.config.checkpoint.warm_start,
                    parse_assignment_map(
                        self.config.checkpoint.warm_start_map))
                # re-anchor any EMA shadow: it snapshotted the discarded
                # fresh init at sync.init time
                state = state.replace(
                    params=params,
                    opt_state=reset_ema(state.opt_state, params))
                self.state = state
                log.info("%s (from %s)", report,
                         self.config.checkpoint.warm_start)
        self._log_attention()
        return state

    def _log_attention(self) -> None:
        """One start-up line: the model's attention path and, for the
        flash kernels, the schedule they chose for this run's shape."""
        impl = getattr(self.model, "attention_impl", None)
        if impl is None:
            return
        detail = ""
        if impl == "flash":
            from ..ops.pallas.flash_attention import describe_attention
            ids = self.train_arrays.get("input_ids")
            seq = (ids.shape[1] if getattr(ids, "ndim", 0) == 2
                   else self.config.data.seq_len)
            detail = ": " + describe_attention(
                seq, self.model.head_dim, self.config.dtype,
                **self.model.attention_kwargs)
        log.info("model %s: attention %s%s", self.config.model, impl, detail)

    def _loader(self, start_step: int | None = None
                ) -> Iterator[dict[str, np.ndarray]]:
        """Batch iterator fast-forwarded to ``start_step`` (default: the
        run's start step). Rollback rebuilds the loader through the same
        exact-resume machinery, aimed at the restored step."""
        if start_step is None:
            start_step = self.start_step
        if hasattr(self.train_arrays, "make_loader"):
            # streaming source (e.g. data.streaming.StreamingSource):
            # batches are materialized on demand instead of held in RAM
            return self.train_arrays.make_loader(
                self.config.data.batch_size,
                start_step=start_step,
                process_index=self.process_index,
                num_processes=self.num_processes,
                shuffle=self.config.data.shuffle,
                seed=self.config.data.seed,
                prefetch=self.config.data.prefetch)
        return make_loader(
            self.train_arrays, self.config.data.batch_size,
            prefetch=self.config.data.prefetch,
            native=self.config.data.native,
            start_step=start_step,        # exact-resume: skip consumed batches
            process_index=self.process_index,
            num_processes=self.num_processes,
            shuffle=self.config.data.shuffle,
            seed=self.config.data.seed,
            transform=self.train_transform)

    # ------------------------------------------------------------------
    def train(self) -> tuple[TrainState, dict[str, Any]]:
        if self.state is None:
            self.initialize()
        # the full resolved config opens THIS run's segment of the
        # metrics stream (the reference printed its flags at launch).
        # The JSONL is append-mode across restarts, so consumers should
        # take the LAST config record at or before a step record — each
        # appended segment is self-describing, not just line 1
        self.metrics_logger.log({
            "config": dataclasses.asdict(self.config),
            "num_processes": self.num_processes,
            "start_step": self.start_step})
        state = self.state
        step = self.start_step
        stop = step >= self.config.train_steps
        device_metrics: dict | None = None
        t_start = time.perf_counter()

        spl = max(1, self.config.steps_per_loop)
        # --step_timing: AOT-compile the dispatch path on the first batch so
        # the cost analysis (flops/bytes) is recorded and per-dispatch times
        # measure a fixed executable; the dispatch itself is timed HERE —
        # perf_counter around the step call + block — so eval/checkpoint/
        # hook time between steps never pollutes the samples (StepTimingHook
        # aggregates trainer.last_dispatch_ms)
        timing = self.config.obs.step_timing
        want_aot = timing
        self.last_dispatch_ms: float | None = None
        # --max_inflight_steps: bound the async dispatch queue. JAX
        # queues dispatches without waiting; N big steps in flight is
        # normally free pipelining, but a runtime that misbehaves under
        # deep queues (the round-4 INVALID_ARGUMENT on the queued long-
        # context causal program — BASELINE.md) gets a first-class cap
        # instead of a hand-rolled workaround
        max_inflight = self.config.max_inflight_steps
        if max_inflight < 0:
            raise ValueError(f"max_inflight_steps must be >= 0, got "
                             f"{max_inflight}")
        pending = 0
        self._rollback_pending = False
        fault_reg = faults.active()
        loader = None
        # --trace_path: arm the span recorder for this train() call and
        # dump the lanes (data/step/checkpoint/rollback) at teardown
        trace_path = self.config.obs.trace_path
        if trace_path:
            obs_trace.ensure_capacity(
                self.config.obs.trace_buffer_events).start()
        try:
            # begin() inside the try: a failing begin (or anything after a
            # partial begin) must still run every hook's end() — hooks
            # with process-global effects (PreemptionHook's signal
            # handlers) would otherwise leak past train()
            for h in self.hooks:
                h.begin(self)
            loader = self._loader()
            while not stop:
                remaining = self.config.train_steps - step
                step_before = step
                if spl > 1 and remaining >= spl:
                    # K steps per dispatch (iterations_per_loop analogue):
                    # stack K host batches on a leading loop axis and scan
                    t_d0 = time.perf_counter()
                    stack = [next(loader) for _ in range(spl)]
                    t_d1 = time.perf_counter()
                    self._h_data_wait.observe(t_d1 - t_d0)
                    add_span("data_wait", t_d0, t_d1,
                             process="training", lane="data", step=step)
                    if fault_reg is not None:
                        # step.* faults poison the HOST batch producing
                        # the matching global step (bad-batch semantics;
                        # the compiled program is untouched)
                        stack = [fault_reg.poison_batch(b, step + i + 1)
                                 for i, b in enumerate(stack)]
                    stacked = {k: np.stack([b[k] for b in stack])
                               for k in stack[0]}
                    batch = self.sync.shard_stacked_batch(stacked)
                    if want_aot:
                        self.sync.precompile(state, batch, multi=True)
                        want_aot = False
                    t0 = time.perf_counter() if timing else 0.0
                    t_s0 = time.perf_counter()
                    state, device_metrics = self.sync.multi_step(state, batch)
                    t_s1 = time.perf_counter()
                    step += spl
                else:
                    t_d0 = time.perf_counter()
                    host_batch = next(loader)
                    t_d1 = time.perf_counter()
                    self._h_data_wait.observe(t_d1 - t_d0)
                    add_span("data_wait", t_d0, t_d1,
                             process="training", lane="data", step=step)
                    if fault_reg is not None:
                        host_batch = fault_reg.poison_batch(host_batch,
                                                            step + 1)
                    batch = self.sync.shard_batch(host_batch)
                    if want_aot:
                        self.sync.precompile(state, batch)
                        want_aot = False
                    t0 = time.perf_counter() if timing else 0.0
                    t_s0 = time.perf_counter()
                    state, device_metrics = self.sync.step(state, batch)
                    t_s1 = time.perf_counter()
                    step += 1
                # dispatch-side span: host time to ENQUEUE the step
                # (the loop is async — device time only shows here
                # under --step_timing, where the block lands below)
                add_span("step_dispatch", t_s0, t_s1,
                         process="training", lane="step", step=step)
                if timing:
                    jax.block_until_ready(state.params)
                    self.last_dispatch_ms = (time.perf_counter() - t0) * 1e3
                elif max_inflight:
                    pending += step - step_before
                    if pending >= max_inflight:
                        jax.block_until_ready(state.params)
                        pending = 0
                self.state = state

                wants = any(h.wants_metrics(step) for h in self.hooks)
                host_metrics = None
                if wants:
                    host_metrics = {
                        k: _host_metric(v)
                        for k, v in jax.device_get(device_metrics).items()}
                for h in self.hooks:
                    if h.after_step(self, step, host_metrics):
                        stop = True

                if self._rollback_pending and not stop:
                    rolled = self._perform_rollback(step, loader)
                    if rolled is None:
                        stop = True            # nothing valid to restore
                    else:
                        state, step, loader = rolled
                        # skip this iteration's eval: the state it would
                        # measure was just discarded
                        continue

                if (self.config.eval_every_steps
                        and step % self.config.eval_every_steps == 0
                        and self.eval_arrays is not None):
                    ev = self.evaluate(state)
                    log.info("eval @ step %d: %s", step,
                             {k: round(v, 4) for k, v in ev.items()})
                    self.metrics_logger.log({"step": step, "eval": ev})
                    self._maybe_save_best(state, step, ev)
                    self._last_eval = (step, ev)
                    if self._early_stop_hit(step, ev):
                        stop = True

            # block on the final step so hook teardown sees settled state
            jax.block_until_ready(state.params)
            wall = time.perf_counter() - t_start
        finally:
            # teardown must run even when a hook raises mid-loop (NanHook's
            # FloatingPointError is its *default* behavior) — the reference's
            # Supervisor shutdown still saved and closed services. A hook
            # end() error must not mask an in-flight loop exception.
            if loader is not None and hasattr(loader, "close"):
                loader.close()       # release the prefetch thread
            import sys as _sys
            in_flight = _sys.exc_info()[0] is not None
            end_error: Exception | None = None
            for h in self.hooks:
                try:
                    h.end(self)
                except Exception as e:
                    # every hook still gets its end(); first error re-raised
                    # after — unless a loop exception is already in flight,
                    # which must not be masked
                    log.exception("hook %s end() failed", type(h).__name__)
                    if end_error is None:
                        end_error = e
            if trace_path:
                rec = obs_trace.recorder()
                rec.stop()
                if jax.process_index() == 0:
                    with open(trace_path, "w") as f:
                        json.dump(rec.to_chrome(), f)
                    log.info("training trace: %s (%d spans)", trace_path,
                             rec.spans_recorded)
            if end_error is not None and not in_flight:
                raise end_error

        summary: dict[str, Any] = {
            "final_step": step,
            "wall_time_sec": wall,
            "steps_per_sec": (step - self.start_step) / wall if wall else 0.0,
        }
        if device_metrics is not None:
            summary["final_metrics"] = {
                k: _host_metric(v)
                for k, v in jax.device_get(device_metrics).items()}
        if self.eval_arrays is not None:
            if self._last_eval is not None and self._last_eval[0] == step:
                # the loop just evaluated this exact step (early stop /
                # cadence landing on the final step): don't pay a second
                # full eval pass on unchanged params
                summary["eval"] = self._last_eval[1]
            else:
                summary["eval"] = self.evaluate(state)
                self._maybe_save_best(state, step, summary["eval"])
        return state, summary

    # ------------------------------------------------------------------
    def request_rollback(self, before_step: int | None = None) -> None:
        """Ask the training loop to restore the last VERIFIED checkpoint
        at the next step boundary (the --on_anomaly=rollback action;
        called by AnomalyPolicyHook). ``before_step`` caps the restore
        target at the last step known anomaly-free, so the replay REDOES
        the anomalous window (with the transient fault gone) instead of
        baking its skipped updates into the trajectory. Deterministic
        across processes: every process observes the same device-computed
        anomaly count at the same cadence, so every process requests
        together with the same cap."""
        self._rollback_pending = True
        self._rollback_before = before_step

    def _perform_rollback(self, step: int, old_loader=None):
        """Restore the newest checkpoint ≤ the requested clean step that
        passes CRC verification, and fast-forward the data stream to it
        (the exact-resume machinery, aimed backward). Returns ``(state,
        step, loader)`` or None when no verified checkpoint exists in
        range (caller halts)."""
        self._rollback_pending = False
        with span("rollback", process="training", lane="rollback",
                  at_step=step):
            return self._perform_rollback_inner(step, old_loader)

    def _perform_rollback_inner(self, step: int, old_loader=None):
        if old_loader is not None and hasattr(old_loader, "close"):
            old_loader.close()      # release the prefetch thread + queue
        before = self._rollback_before
        mgr = self.ckpt_manager
        mgr.wait()
        # run-scoped accounting, not model state: the budget must keep
        # charging across the restore or a divergence loop would spin
        # rollbacks forever inside a never-spent budget
        pre_count = self.state.anomaly_count
        if self.num_processes > 1:
            # multi-host: the chief's verification read picks the step,
            # every process then restores it — the probe read is the
            # price of the broadcast agreement
            from ..ckpt.checkpoint import _agreed_latest_step
            target = _agreed_latest_step(mgr, max_step=before)
            if target is None:
                log.error("rollback requested at step %d but no verified "
                          "checkpoint at or before clean step %s exists "
                          "under %r — halting", step, before, mgr.directory)
                return None
            state = mgr.restore(self.state, step=target)
        else:
            # single-process: verify WHILE restoring (one read of the
            # chosen checkpoint, walking past corrupt candidates)
            try:
                state = mgr.restore(self.state, step=None, max_step=before)
            except FileNotFoundError as e:   # incl. CorruptCheckpointError
                log.error("rollback requested at step %d but no verified "
                          "checkpoint at or before clean step %s exists "
                          "under %r (%s) — halting",
                          step, before, mgr.directory, e)
                return None
            target = int(jax.device_get(state.step))
        state = state.replace(anomaly_count=pre_count)
        self.state = state
        # truncate the rejected trajectory: checkpoints newer than the
        # restore target embed the skipped-update window — a preemption
        # during the replay must not hand restore_or_init the very
        # trajectory this rollback discarded
        discarded = mgr.discard_steps_above(target)
        if discarded:
            log.warning("rollback: discarded rejected-trajectory "
                        "checkpoint step(s) %s", discarded)
        loader = self._loader(start_step=target)
        log.warning("rollback: restored verified checkpoint step %d "
                    "(training was at step %d); data stream "
                    "fast-forwarded to match", target, step)
        return state, target, loader

    # early-stop progress survives preemption in a sidecar next to the
    # checkpoints (the counters are host-side floats, not state leaves)
    def _early_stop_path(self) -> str | None:
        d = self.config.checkpoint.directory
        return os.path.join(d, "early_stop.json") if d else None

    def _early_stop_save(self) -> None:
        path = self._early_stop_path()
        if path is None or jax.process_index() != 0:
            return
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"best": self._early_best,
                       "misses": self._early_misses}, f)
        os.replace(tmp, path)

    def _early_stop_load(self) -> None:
        path = self._early_stop_path()
        if path is None or not os.path.exists(path):
            return
        with open(path) as f:
            st = json.load(f)
        self._early_best = st.get("best")
        self._early_misses = int(st.get("misses", 0))
        log.info("early-stop state restored: best=%s misses=%d",
                 self._early_best, self._early_misses)

    def _early_stop_hit(self, step: int, ev: dict) -> bool:
        """stop_if_no_decrease_hook parity: True once the tracked eval
        metric has gone ``early_stop_patience`` evals without improving.
        NaN evals count as misses (they improve on nothing)."""
        metric = self.config.early_stop_metric
        if not metric:
            return False
        if metric not in ev:
            raise ValueError(
                f"early_stop_metric={metric!r} is not an eval metric "
                f"(eval produced {sorted(ev)})")
        value = float(ev[metric])
        if jax.process_count() > 1:
            # cross-host agreement: the verdict chain (best/misses/stop)
            # must be identical on every process or a bitwise eval
            # divergence desynchronizes the training loops (hang at the
            # next collective) — same discipline as save_best's
            # broadcast (ADVICE r3 #3)
            from jax.experimental import multihost_utils
            value = float(multihost_utils.broadcast_one_to_all(
                np.float64(value)))
        better = (not math.isnan(value)) and (
            self._early_best is None
            or (value > self._early_best
                if self.config.early_stop_mode == "max"
                else value < self._early_best))
        if better:
            self._early_best = value
            self._early_misses = 0
            self._early_stop_save()
            return False
        self._early_misses += 1
        self._early_stop_save()
        if self._early_misses >= self.config.early_stop_patience:
            log.info("early stop at step %d: %s did not improve for %d "
                     "evals (best %s)", step, metric,
                     self._early_misses, self._early_best)
            return True
        return False

    def _maybe_save_best(self, state: TrainState, step: int,
                         ev: dict) -> None:
        """BestExporter parity: track the best eval metric and keep its
        checkpoint immune from ring rotation."""
        metric = self.config.checkpoint.keep_best_metric
        if not metric or self.ckpt_manager is None:
            return
        if metric not in ev:
            raise ValueError(
                f"keep_best_metric={metric!r} is not an eval metric "
                f"(eval produced {sorted(ev)})")
        if self.ckpt_manager.save_best(
                state, step, float(ev[metric]),
                mode=self.config.checkpoint.keep_best_mode):
            log.info("new best %s=%.6f at step %d", metric,
                     float(ev[metric]), step)

    # ------------------------------------------------------------------
    def close(self) -> None:
        """Release owned resources (the metrics JSONL handle, the async
        checkpoint writer, an installed fault registry). The Trainer owns
        these — hooks must not close them. A pending async-save error
        SURFACES from ckpt_manager.close(); the remaining resources are
        still released first (a failed final write must not also leak
        the decode pool or leave fault injection armed for the next
        Trainer in this process)."""
        # each resource releases regardless of the others failing — a
        # metrics-flush ENOSPC must not leave the fault registry armed
        # for the next Trainer in this process, or leak the decode pool
        try:
            self.metrics_logger.close()
        finally:
            try:
                if hasattr(self.train_arrays, "close"):
                    self.train_arrays.close()  # streaming: decode pool
            finally:
                try:
                    if self._faults_installed:
                        faults.install(None)
                        self._faults_installed = False
                finally:
                    if self.ckpt_manager is not None:
                        # raises a pending async write error (once)
                        self.ckpt_manager.close()

    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------
    def evaluate(self, state: TrainState,
                 batch_size: int | None = None,
                 use_ema: bool | None = None) -> dict[str, float]:
        """Forward-only metrics over the eval set (the reference's final
        test-accuracy pass, SURVEY.md §2.1 'Train loop + eval').

        When ``ema_decay`` is on, eval runs on the shadow parameters (the
        ``ema.variables_to_restore()`` eval recipe); pass
        ``use_ema=False`` to eval the live params instead.

        Static-shape discipline: the tail batch is padded up to ``bs`` with
        repeated rows and excluded via a ``__valid__`` example mask that
        every model's ``eval_metrics`` honors — so the whole pass runs ONE
        compiled executable regardless of eval-set size (no per-tail-shape
        recompile; ``self._eval_fn._cache_size() == 1``)."""
        if self._eval_fn is None:
            def eval_metrics(params, extras, batch):
                # same ambient mesh as the train step (SyncReplicas)
                with jax.sharding.use_abstract_mesh(
                        self.mesh.abstract_mesh):
                    return self.model.eval_metrics(params, extras, batch)
            self._eval_fn = jax.jit(eval_metrics)
        params = state.params
        explicit = use_ema is not None
        if use_ema is None:
            use_ema = self.config.optimizer.ema_decay > 0
        if use_ema:
            ema = find_ema_params(state.opt_state)
            if ema is not None:
                params = ema
            elif explicit:
                raise ValueError(
                    "use_ema=True but the optimizer state holds no EMA "
                    "shadow (ema_decay is 0 for this run)")
        bs = batch_size or self.config.data.batch_size
        n = len(next(iter(self.eval_arrays.values())))
        # bs stays the configured (mesh-divisible) batch even when the eval
        # set is smaller: a single padded+masked batch keeps the sharding
        # legal and the executable static
        totals: dict[str, float] = {}
        count = 0
        for i in range(0, n, bs):
            batch = {k: v[i:i + bs] for k, v in self.eval_arrays.items()}
            m = len(next(iter(batch.values())))
            if m < bs:
                # pad with copies of row 0 (content is irrelevant — the
                # mask zeroes its contribution); keeps the batch shape and
                # therefore the sharding/executable static
                batch = {k: np.concatenate(
                    [v, np.repeat(v[:1], bs - m, axis=0)])
                    for k, v in batch.items()}
            mask = np.zeros((bs,), np.float32)
            mask[:m] = 1.0
            batch["__valid__"] = mask
            placed = self.sync.shard_batch(batch)
            out = jax.device_get(
                self._eval_fn(params, state.extras, placed))
            for k, v in out.items():
                totals[k] = totals.get(k, 0.0) + float(v) * m
            count += m
        return {k: v / count for k, v in totals.items()} if count else {}

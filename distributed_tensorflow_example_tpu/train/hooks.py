"""Training hooks — the ``basic_session_run_hooks`` family (SURVEY.md §2.2).

Parity map (reference → here):

- ``LoggingTensorHook`` (:169)    → :class:`LoggingHook`
- ``StopAtStepHook`` (:393)       → :class:`StopAtStepHook`
- ``CheckpointSaverHook`` (:524)  → :class:`CheckpointSaverHook` (also covers
  the Supervisor's SVTimerCheckpointThread via ``save_secs``)
- ``StepCounterHook`` (:674)      → :class:`StepCounterHook`
- ``NanTensorHook`` (:761)        → :class:`NanHook`
- ``SummarySaverHook`` (:793)     → :class:`SummaryHook` (JSONL, §5.5)
- ``GlobalStepWaiterHook`` (:902) → :class:`GlobalStepWaiterHook` (no-op on
  TPU: it staggered *async* workers; SPMD replicas are lockstep by
  construction — kept for API compatibility)
- ``ProfilerHook`` (:1013)        → :class:`ProfilerHook` (jax.profiler
  traces instead of chrome-trace RunMetadata, §5.1)

Contract: hooks run on every process but side-effecting hooks act only on
the chief (process 0), mirroring the chief-only Supervisor services
(SURVEY.md §3.2). ``after_step`` may return ``True`` to request a stop
(the Coordinator's should_stop analogue).

Hooks that need metric *values* declare ``every_steps``; the trainer only
materializes device metrics on steps where some hook wants them, so the
steady-state loop stays free of host syncs.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import numpy as np

from ..ckpt.checkpoint import CheckpointManager
from ..obs.trace import span
from ..utils.logging import get_logger
from ..utils.metrics import MetricsLogger, RateTracker

log = get_logger("hooks")


def _is_chief() -> bool:
    return jax.process_index() == 0


class Hook:
    every_steps: int = 0      # 0 => never needs materialized metrics

    def begin(self, trainer) -> None: ...
    def after_step(self, trainer, step: int,
                   metrics: dict[str, float] | None) -> bool | None: ...
    def end(self, trainer) -> None: ...

    def wants_metrics(self, step: int) -> bool:
        return self.every_steps > 0 and step % self.every_steps == 0


class LoggingHook(Hook):
    """Print selected metrics every N steps (LoggingTensorHook parity)."""

    def __init__(self, every_steps: int = 100, keys: list[str] | None = None):
        self.every_steps = every_steps
        self.keys = keys

    def after_step(self, trainer, step, metrics):
        if metrics is None or not self.wants_metrics(step) or not _is_chief():
            return
        keys = self.keys or [k for k in metrics if k != "step"]
        body = " ".join(f"{k}={metrics[k]:.6g}" for k in keys
                        if k in metrics and np.ndim(metrics[k]) == 0)
        log.info("step %d: %s", step, body)


class StopAtStepHook(Hook):
    def __init__(self, last_step: int):
        self.last_step = last_step

    def after_step(self, trainer, step, metrics):
        return step >= self.last_step


class StepCounterHook(Hook):
    """steps/sec + examples/sec(/chip) every N steps."""

    def __init__(self, every_steps: int = 100, batch_size: int = 0,
                 metrics_logger: MetricsLogger | None = None):
        self.every_steps = every_steps
        self.tracker = RateTracker(batch_size)
        self.metrics_logger = metrics_logger
        self.last_rates: dict[str, float] = {}

    def begin(self, trainer):
        self.tracker.start(int(trainer.start_step))

    def after_step(self, trainer, step, metrics):
        if self.every_steps <= 0 or step % self.every_steps:
            return
        self.last_rates = self.tracker.rates(step)
        if not self.last_rates or not _is_chief():
            return
        # the reference-era dashboards always carried a learning_rate
        # scalar next to steps/sec; the schedule is host-evaluable
        lr = getattr(trainer, "learning_rate_at", None)
        if lr is not None:
            self.last_rates["learning_rate"] = lr(step)
        log.info("step %d: %.1f steps/s, %s", step,
                 self.last_rates["steps_per_sec"],
                 (f"{self.last_rates['examples_per_sec_per_chip']:.1f} "
                  "examples/s/chip"
                  if "examples_per_sec_per_chip" in self.last_rates else ""))
        if self.metrics_logger:
            self.metrics_logger.log({"step": step, **self.last_rates})

    def wants_metrics(self, step):
        # never needs metric *values* — don't force a device→host sync
        # (rates are wall-clock only; the async dispatch queue stays full)
        return False


class CheckpointSaverHook(Hook):
    """Save every N steps and/or T seconds; always saves at end.

    Chief-only writes are enforced inside CheckpointManager (SURVEY.md
    §3.4: non-chief never writes)."""

    def __init__(self, manager: CheckpointManager, *,
                 save_steps: int = 0, save_secs: float = 0.0):
        self.manager = manager
        self.save_steps = save_steps
        self.save_secs = save_secs
        self._last_save_t = time.time()
        self._last_saved_step: int | None = None
        # save() is a cross-process collective for non-addressable (fsdp)
        # arrays, so the *decision* to save must be identical on every
        # process. Wall-clock cadence is per-process (clock/loop skew) and
        # would deadlock a multi-host run; step cadence is deterministic.
        if save_secs and jax.process_count() > 1:
            raise ValueError(
                "save_secs is wall-clock-based and not deterministic across "
                "processes (risk of collective deadlock in save()); use "
                "save_steps on multi-host runs")

    def _due(self, step: int) -> bool:
        if self.save_steps and step % self.save_steps == 0:
            return True
        if self.save_secs and time.time() - self._last_save_t >= self.save_secs:
            return True
        return False

    def _save(self, trainer, step: int) -> None:
        """One checkpoint save, a span on the trainer's checkpoint
        trace lane."""
        with span("checkpoint_save", process="training",
                  lane="checkpoint", step=step):
            self.manager.save(trainer.state, step)

    def after_step(self, trainer, step, metrics):
        if self._due(step):
            self._save(trainer, step)
            self._last_saved_step = step
            self._last_save_t = time.time()

    def end(self, trainer):
        # deterministic across processes: depends only on step history.
        # No-progress guard: if this train() call never advanced the step
        # (e.g. startup failed before the first dispatch), there is nothing
        # new to capture — and saving WOULD be harmful: a fresh-init
        # ckpt-0 written by a failed launch hijacks the next run's
        # restore-or-init
        step = int(jax.device_get(trainer.state.step))
        if step != trainer.start_step and self._last_saved_step != step:
            self._save(trainer, step)
            self._last_saved_step = step
        self.manager.wait()        # async writes must land before exit


class AnomalyPolicyHook(Hook):
    """The --on_anomaly policy driver (halt | skip | rollback).

    Detection itself is ON-DEVICE (SyncReplicas folds a finite-check of
    loss and global grad-norm into the compiled step and carries a
    cumulative ``anomaly_count`` in TrainState), so this hook adds NO
    per-step host sync: it observes the count at the metrics cadence the
    LoggingHook already materializes (``every_steps``), which means a
    healthy run's dispatch queue is untouched and an anomalous run is
    acted on at most one cadence window late — by which point the
    on-device identity update has already kept the bad step out of the
    training state. NanHook (per-step sync, raises at the exact step)
    remains the debug fallback.

    Policies, on observing new anomalies:

    - ``halt``: log a summary and request a clean stop (the state holds
      the last-good params — the identity update never let the
      non-finite step in — so the end-of-run checkpoint is sound).
    - ``skip``: keep training (the device already skipped the bad
      updates); halt with a summary once the run's anomaly budget
      (``max_anomalies``) is exceeded.
    - ``rollback``: ask the Trainer to restore the last verified
      checkpoint and replay the data stream (Megatron-style
      skip-bad-step + rollback-on-divergence practice); budget as above.
    """

    def __init__(self, policy: str, max_anomalies: int,
                 every_steps: int = 100):
        if policy not in ("halt", "skip", "rollback"):
            raise ValueError(f"unknown anomaly policy {policy!r}")
        self.policy = policy
        self.max_anomalies = max_anomalies
        self.every_steps = max(1, every_steps)
        self.observed = 0       # device-counter watermark (cumulative)
        self.baseline = 0       # counter value when this run began
        self.last_clean_step = 0

    def begin(self, trainer):
        # budget window = this train() call: anomalies a restored
        # checkpoint carries from an earlier incarnation are history,
        # not charges against this run's budget — the budget compares
        # against (counter - baseline), never the raw counter
        self.observed = self.baseline = (
            int(jax.device_get(trainer.state.anomaly_count))
            if trainer.state is not None else 0)
        self.last_clean_step = int(getattr(trainer, "start_step", 0) or 0)

    def _summary(self, step: int, total: int) -> str:
        return (f"anomaly policy {self.policy!r}: {total} anomalous "
                f"step(s) (non-finite loss or grad-norm) observed by "
                f"step {step}; every one was excluded from the training "
                "state by the on-device identity update. Rerun with "
                "--check_nans (exact step) or --debug_checks (exact op) "
                "to localize the source.")

    def after_step(self, trainer, step, metrics):
        if metrics is None or not self.wants_metrics(step):
            return
        count = int(metrics.get("anomaly_count", 0))
        if count <= self.observed:
            # every step up to here verified finite: a future rollback
            # must not land past this point, or the anomalous window
            # (whose updates were skipped) would be baked into the
            # restored trajectory instead of repaired by the replay
            self.last_clean_step = step
            return
        self.observed = count
        total = count - self.baseline      # THIS run's anomalies only
        if self.policy == "halt":
            log.error("%s — halting (state holds the last finite "
                      "update).", self._summary(step, total))
            return True
        if total > self.max_anomalies:
            log.error("%s Budget --max_anomalies=%d EXCEEDED — halting.",
                      self._summary(step, total), self.max_anomalies)
            return True
        if self.policy == "skip":
            log.warning("%s Continuing (%d/%d of the anomaly budget "
                        "spent).", self._summary(step, total), total,
                        self.max_anomalies)
            return
        log.warning("%s Requesting rollback to the last verified "
                    "checkpoint at or before clean step %d (%d/%d of the "
                    "anomaly budget spent).", self._summary(step, total),
                    self.last_clean_step, total, self.max_anomalies)
        trainer.request_rollback(before_step=self.last_clean_step)


class NanHook(Hook):
    """Stop (or raise) on NaN/Inf loss — NanTensorHook parity. Forces a
    per-step host sync; enable only when debugging (obs.check_nans)."""

    every_steps = 1

    def __init__(self, fail_on_nan: bool = True):
        self.fail_on_nan = fail_on_nan

    def after_step(self, trainer, step, metrics):
        if metrics is None:
            return
        loss = metrics.get("loss")
        if loss is not None and not np.isfinite(loss):
            msg = f"non-finite loss {loss} at step {step}"
            if self.fail_on_nan:
                raise FloatingPointError(msg)
            log.error("%s — requesting stop", msg)
            return True


class SummaryHook(Hook):
    """Write scalar metrics to the JSONL sink every N steps
    (SummarySaverHook / summary-thread parity, SURVEY.md §5.5)."""

    def __init__(self, metrics_logger: MetricsLogger, every_steps: int = 100):
        self.metrics_logger = metrics_logger
        self.every_steps = every_steps

    def after_step(self, trainer, step, metrics):
        if metrics is None or not self.wants_metrics(step):
            return
        self.metrics_logger.log({"step": step, **metrics})
    # note: the MetricsLogger is owned by its creator (Trainer.close()
    # releases it); this hook must not close a logger it was handed


class ParamHistogramHook(Hook):
    """Write parameter-distribution histograms every N steps
    (``tf.summary.histogram`` on trainable variables — the reference
    era's weight-histogram dashboards). Opt-in: pulls params to host at
    the cadence, so keep the interval generous for big models.

    Multi-host: the host gather is collective (``_to_host``
    process-allgathers non-addressable fsdp/tp shards — every process
    must enter it, like checkpoint.save); the stats/logging loop itself
    is chief-only per the module contract."""

    def __init__(self, metrics_logger: MetricsLogger, every_steps: int):
        self.metrics_logger = metrics_logger
        self.every_steps = every_steps

    def wants_metrics(self, step: int) -> bool:
        return False          # reads trainer.state, never step metrics

    def after_step(self, trainer, step, metrics):
        if self.every_steps <= 0 or step % self.every_steps:
            return
        import jax

        from ..ckpt.checkpoint import _to_host
        from ..utils.pytree import path_str
        params = jax.tree_util.tree_map(_to_host, trainer.state.params)
        if jax.process_index() != 0:
            return
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            self.metrics_logger.log_histogram(
                step, "params/" + path_str(path), leaf)


class GlobalStepWaiterHook(Hook):
    """Reference: delayed async-worker starts until the chief advanced the
    global step (basic_session_run_hooks.py:902). SPMD sync training has no
    async stagger; kept as an explicit no-op so launch configs port."""

    def __init__(self, wait_until_step: int = 0):
        self.wait_until_step = wait_until_step

    def begin(self, trainer):
        if self.wait_until_step:
            log.info("GlobalStepWaiterHook is a no-op under SPMD sync "
                     "training (wait_until_step=%d ignored)",
                     self.wait_until_step)


class PreemptionHook(Hook):
    """Graceful shutdown on SIGTERM/SIGINT: finish the in-flight step,
    request a clean loop stop, and let ``CheckpointSaverHook.end()`` write
    the final checkpoint — the Supervisor's stop→save semantics
    (SURVEY.md §3.4/§3.5) applied to the TPU world, where the signal is
    typically a VM maintenance-event notice.

    Single process: a Python signal handler turns SIGTERM into
    "checkpoint at the step boundary and exit 0".

    Multi-process: a one-host Python-level stop would leave the other
    hosts blocked in a collective, so the hook instead rides the TSL
    coordination service's preemption protocol (the same C++ service the
    reference's modern failure detection uses, SURVEY.md §5.3): the TSL
    preemption notifier owns SIGTERM (installed by
    ``jax.distributed.initialize``), the notice is broadcast through the
    coordination service, and ``reached_preemption_sync_point(step)``
    returns True on EVERY process at the SAME future step boundary — all
    hosts stop together, all participate in the final (possibly
    process_allgather-ing or sharded) checkpoint save, and all exit 0.
    A SIGTERM to ANY ONE process therefore checkpoints the whole cluster.
    """

    def __init__(self, signals: tuple[int, ...] | None = None):
        import signal as _signal
        self.signals = signals or (_signal.SIGTERM, _signal.SIGINT)
        self.stop_requested = False
        self._prev: dict[int, Any] = {}
        self._multiprocess = False
        self._last_polled: int | None = None

    def begin(self, trainer):
        import signal as _signal
        self.stop_requested = False   # a prior run's stop must not leak
                                      # into a resumed train() call
        self._multiprocess = jax.process_count() > 1
        # seed the poll window from the run's start step: with
        # steps_per_loop > 1 the first after_step sees step ==
        # start+K, and starting the poll AT that boundary would skip
        # ids start+1..start+K-1 — exactly the unpolled gap the loop
        # below exists to close (a SIGTERM during the first loop could
        # set the sync point inside it and the stop would never fire)
        self._last_polled = int(getattr(trainer, "start_step", 0) or 0)
        if self._multiprocess:
            # SIGTERM belongs to the TSL preemption notifier here; a
            # Python handler would steal the signal from the cross-host
            # sync protocol (after_step polls the sync point instead)
            return

        def handler(signum, frame):
            if self.stop_requested:
                # second signal: the boundary never came (hung loader or
                # device wait) — restore the previous disposition and
                # re-raise so the user can actually stop the process
                _signal.signal(signum,
                               self._prev.get(signum, _signal.SIG_DFL))
                log.warning("second signal %d: restoring default "
                            "handling", signum)
                _signal.raise_signal(signum)
                return
            log.warning("signal %d: stopping at the next step boundary "
                        "(checkpoint will be written); send again to "
                        "force", signum)
            self.stop_requested = True

        try:
            for s in self.signals:
                self._prev[s] = _signal.signal(s, handler)
        except ValueError:
            # not the main thread (e.g. Trainer driven from a test
            # harness thread): signals can't be installed — undo any
            # partial install and stay inert
            self.end(trainer)

    def after_step(self, trainer, step, metrics):
        if self._multiprocess and not self.stop_requested:
            from jax.experimental import multihost_utils
            # the sync protocol's contract is one call per TRAINING step
            # with consecutive ids (the safe step is max reported + 1 and
            # fires on equality) — under steps_per_loop > 1 the loop
            # advances K at a time, so poll every id in the gap or the
            # safe step could fall between observed boundaries and the
            # stop would silently never fire
            start = (self._last_polled + 1 if self._last_polled is not None
                     else int(step))   # None only if begin() never ran
            for s in range(start, int(step) + 1):
                if multihost_utils.reached_preemption_sync_point(s):
                    log.warning("preemption sync point at step %d: all "
                                "processes stopping (checkpoint will be "
                                "written)", step)
                    self.stop_requested = True
                    break
            self._last_polled = int(step)
        return self.stop_requested or None

    def end(self, trainer):
        import signal as _signal
        for s, prev in self._prev.items():
            _signal.signal(s, prev)
        self._prev.clear()


class StepTimingHook(Hook):
    """Per-dispatch device-time records — the WorkerCacheLogger analogue
    (SURVEY.md §2.2 WorkerCacheLogger row, §5.1).

    The reference logged per-step RecvTensor start/end usecs into a
    timeline; under SPMD the per-step observable is the compiled step's
    device latency. The Trainer measures each dispatch (perf_counter
    around the step call + block_until_ready, so eval/checkpoint/hook
    time between steps is NOT attributed — see ``last_dispatch_ms``) and
    this hook aggregates: every ``every_steps`` *trained steps* worth of
    dispatches it writes a percentile summary to the metrics JSONL —
    plus, once, the compiled executable's static cost analysis
    (flops / bytes accessed) captured by :meth:`SyncReplicas.precompile`.
    Blocking defeats the async dispatch queue (documented overhead) —
    opt-in via ``--step_timing``.
    """

    def __init__(self, metrics_logger: MetricsLogger | None,
                 every_steps: int = 100):
        self.every_steps = every_steps
        self.metrics_logger = metrics_logger
        self._times_ms: list[float] = []
        self._first_ms: float | None = None   # includes compile time
        self._cost_logged = False
        self.last_record: dict | None = None

    def after_step(self, trainer, step, metrics):
        dt_ms = getattr(trainer, "last_dispatch_ms", None)
        if dt_ms is None:
            return
        if self._first_ms is None:
            self._first_ms = dt_ms       # first dispatch (may include a
            return                       # compile); kept out of the stats
        self._times_ms.append(dt_ms)
        spd = max(1, getattr(trainer.config, "steps_per_loop", 1))
        # cadence in dispatches, not raw step numbers: with K steps per
        # dispatch, step only hits multiples of lcm(K, every_steps)
        if len(self._times_ms) >= max(1, self.every_steps // spd):
            self._emit(trainer, step, spd)

    def _emit(self, trainer, step: int, steps_per_dispatch: int) -> None:
        if not self._times_ms:
            return
        arr = np.asarray(self._times_ms)
        rec: dict[str, Any] = {"step": step, "step_timing_ms": {
            "n": int(arr.size),
            "steps_per_dispatch": steps_per_dispatch,
            "mean": float(arr.mean()),
            "p50": float(np.percentile(arr, 50)),
            "p90": float(np.percentile(arr, 90)),
            "p99": float(np.percentile(arr, 99)),
            "max": float(arr.max()),
            "first_dispatch_ms": float(self._first_ms),
        }}
        if not self._cost_logged:
            cost = getattr(trainer.sync, "last_cost_analysis", None)
            if cost:
                rec["step_cost_analysis"] = cost
                rec["compile_seconds"] = trainer.sync.last_compile_seconds
                self._cost_logged = True
        self.last_record = rec
        self._times_ms.clear()
        if _is_chief():
            log.info("step %d: dispatch p50=%.3fms p99=%.3fms (n=%d)",
                     step, rec["step_timing_ms"]["p50"],
                     rec["step_timing_ms"]["p99"], arr.size)
            if self.metrics_logger:
                self.metrics_logger.log(rec)

    def end(self, trainer):
        # flush the residue so --step_timing always yields >= 1 record
        # (short runs, or steps_per_loop not dividing every_steps)
        step = int(jax.device_get(trainer.state.step))
        self._emit(trainer, step,
                   max(1, getattr(trainer.config, "steps_per_loop", 1)))

    def wants_metrics(self, step):
        # consumes trainer-measured dispatch times, not metric values
        return False


class ProfilerHook(Hook):
    """Capture a jax.profiler trace for steps in [start, stop)
    (ProfilerHook/timeline parity, SURVEY.md §5.1)."""

    def __init__(self, profile_dir: str, start_step: int, stop_step: int):
        self.profile_dir = profile_dir
        self.start_step = start_step
        self.stop_step = stop_step
        self._active = False

    def after_step(self, trainer, step, metrics):
        if not _is_chief():
            return
        if not self._active and step >= self.start_step and step < self.stop_step:
            jax.profiler.start_trace(self.profile_dir)
            self._active = True
        elif self._active and step >= self.stop_step:
            jax.profiler.stop_trace()
            self._active = False

    def end(self, trainer):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False

    def wants_metrics(self, step):
        # needs step boundaries around the window, not values
        return False

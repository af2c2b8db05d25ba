#!/usr/bin/env python
"""Chaos soak: seeded kill/corrupt/NaN/flaky-IO scenarios with asserted
recovery invariants — the repo's systematic robustness gate.

Each scenario runs real Trainers (CPU mesh works: ``JAX_PLATFORMS=cpu``
+ ``XLA_FLAGS=--xla_force_host_platform_device_count=4``) through a
deterministic failure and asserts the self-healing contract:

- ``kill_resume``     — clean kill at step K, restart, run to N: final
                        params BIT-match an uninterrupted run (the
                        pre-existing exact-resume guarantee, kept honest
                        under the new verified-restore path).
- ``corrupt_latest``  — the newest checkpoint file is truncated on disk
                        (and, separately, zero-filled): restart restores
                        the previous VALID step and still converges.
- ``nan_skip``        — an injected NaN batch under --on_anomaly=skip:
                        same final step as the clean run, loss stream
                        finite throughout, anomaly_count == 1.
- ``nan_rollback``    — an injected divergence under
                        --on_anomaly=rollback: the run restores the last
                        clean checkpoint, replays, and its FINAL PARAMS
                        match the uninterrupted run (divergence
                        repaired, not merely survived).
- ``flaky_io``        — probabilistic loader faults under the bounded
                        retry+backoff policy: the run completes with
                        zero anomalies.
- ``budget_halt``     — more injected NaN steps than --max_anomalies:
                        the run halts early instead of limping on.
- ``torn_write``      — fault-injected torn checkpoint writes
                        (corrupt=truncate): a restart falls back past
                        every damaged file to the newest valid one.

Usage::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python experiments/chaos_soak.py [--scenario all] [--seed 0] \
        [--steps 20]

Prints one JSON line per scenario: {"scenario", "ok", "detail"}. Exits
nonzero if any scenario fails. tests/test_chaos_soak.py runs the full
soak as a ``slow`` test; tests/test_self_healing.py keeps a fast smoke
of the same invariants in tier-1.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from distributed_tensorflow_example_tpu.config import (CheckpointConfig,
                                                       DataConfig, MeshShape,
                                                       ObservabilityConfig,
                                                       OptimizerConfig,
                                                       TrainConfig)
from distributed_tensorflow_example_tpu.data.mnist import synthetic_mnist
from distributed_tensorflow_example_tpu.models import get_model
from distributed_tensorflow_example_tpu.parallel.mesh import local_mesh
from distributed_tensorflow_example_tpu.train import hooks as hooks_lib
from distributed_tensorflow_example_tpu.train.trainer import Trainer

MESH = 4


def make_config(*, steps: int, seed: int, ckpt_dir: str | None = None,
                save_steps: int = 0, on_anomaly: str = "halt",
                max_anomalies: int = 10, fault_spec: str = "",
                log_every: int = 5) -> TrainConfig:
    return TrainConfig(
        model="mlp", train_steps=steps, mesh=MeshShape(data=MESH),
        data=DataConfig(batch_size=64, seed=seed + 1),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.1),
        checkpoint=CheckpointConfig(directory=ckpt_dir,
                                    save_steps=save_steps),
        obs=ObservabilityConfig(log_every_steps=log_every),
        on_anomaly=on_anomaly, max_anomalies=max_anomalies,
        fault_spec=fault_spec, seed=seed)


class LossStream(hooks_lib.Hook):
    """Collect every step's materialized loss (forces per-step metrics —
    a test instrument, not a production pattern)."""

    every_steps = 1

    def __init__(self):
        self.losses: list[float] = []

    def after_step(self, trainer, step, metrics):
        if metrics is not None:
            self.losses.append(float(metrics["loss"]))


def run_trainer(cfg: TrainConfig, data, hooks=None):
    model = get_model("mlp", cfg)
    trainer = Trainer(model, cfg,
                      {"x": data["train_x"], "y": data["train_y"]},
                      mesh=local_mesh(MESH), process_index=0,
                      num_processes=1, hooks=hooks)
    with trainer:
        state, summary = trainer.train()
    return state, summary


def host_params(state):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(state.params))


def assert_params_equal(a, b, what: str, rtol=1e-6, atol=1e-7):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(x, y, rtol=rtol, atol=atol,
                                                err_msg=what),
        host_params(a), host_params(b))


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

def scenario_kill_resume(data, seed: int, steps: int) -> str:
    ref_state, _ = run_trainer(make_config(steps=steps, seed=seed), data)
    d = tempfile.mkdtemp(prefix="chaos_kill_")
    run_trainer(make_config(steps=steps // 2, seed=seed, ckpt_dir=d,
                            save_steps=5), data)        # the "killed" run
    state, summary = run_trainer(
        make_config(steps=steps, seed=seed, ckpt_dir=d, save_steps=5),
        data)
    assert summary["final_step"] == steps, summary["final_step"]
    assert_params_equal(state, ref_state, "kill/resume parity")
    return f"resumed at {steps // 2}, parity at {steps}"


def _damage(path: str, mode: str) -> None:
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        if mode == "truncate":
            f.truncate(max(1, size // 2))
        else:
            f.seek(size // 3)
            f.write(b"\0" * max(1, size // 3))


def scenario_corrupt_latest(data, seed: int, steps: int) -> str:
    details = []
    for mode in ("truncate", "zero"):
        d = tempfile.mkdtemp(prefix=f"chaos_corrupt_{mode}_")
        cfg = make_config(steps=steps, seed=seed, ckpt_dir=d, save_steps=5)
        run_trainer(cfg, data)
        from distributed_tensorflow_example_tpu.ckpt.checkpoint import \
            CheckpointManager
        mgr = CheckpointManager(d)
        latest = mgr.latest_step()
        _damage(mgr.checkpoint_path(latest), mode)
        # restart: must fall back to the previous valid step, not crash
        model = get_model("mlp", cfg)
        trainer = Trainer(model, cfg,
                          {"x": data["train_x"], "y": data["train_y"]},
                          mesh=local_mesh(MESH), process_index=0,
                          num_processes=1)
        with trainer:
            trainer.initialize()
            start = trainer.start_step
        assert 0 < start < latest, (start, latest)
        details.append(f"{mode}: {latest}->{start}")
    return "; ".join(details)


def scenario_nan_skip(data, seed: int, steps: int) -> str:
    bad_step = steps // 2 + 1
    stream = LossStream()
    _, ref = run_trainer(make_config(steps=steps, seed=seed), data)
    state, summary = run_trainer(
        make_config(steps=steps, seed=seed, on_anomaly="skip",
                    fault_spec=f"step.nan:step={bad_step}"),
        data, hooks=[stream])
    assert summary["final_step"] == ref["final_step"], summary["final_step"]
    assert all(np.isfinite(l) for l in stream.losses), stream.losses
    count = int(summary["final_metrics"]["anomaly_count"])
    assert count == 1, count
    return (f"NaN at step {bad_step} skipped; {len(stream.losses)} finite "
            "losses")


def scenario_nan_rollback(data, seed: int, steps: int) -> str:
    bad_step = steps // 2 + 1
    ref_state, _ = run_trainer(make_config(steps=steps, seed=seed), data)
    d = tempfile.mkdtemp(prefix="chaos_rollback_")
    state, summary = run_trainer(
        make_config(steps=steps, seed=seed, ckpt_dir=d, save_steps=5,
                    on_anomaly="rollback",
                    fault_spec=f"step.nan:step={bad_step}"), data)
    assert summary["final_step"] == steps, summary["final_step"]
    assert int(summary["final_metrics"]["anomaly_count"]) == 1
    # the strong contract: replaying the repaired window converges to the
    # SAME final params as a run that never saw the fault
    assert_params_equal(state, ref_state, "rollback divergence repair")
    return f"NaN at {bad_step} rolled back + replayed to parity"


def scenario_flaky_io(data, seed: int, steps: int) -> str:
    state, summary = run_trainer(
        make_config(steps=steps, seed=seed, on_anomaly="skip",
                    fault_spec="loader.next:p=0.2"), data)
    assert summary["final_step"] == steps, summary["final_step"]
    assert int(summary["final_metrics"]["anomaly_count"]) == 0
    return f"{steps} steps through p=0.2 loader faults (retried)"


def scenario_budget_halt(data, seed: int, steps: int) -> str:
    spec = ";".join(f"step.nan:step={s}" for s in range(2, steps, 2))
    state, summary = run_trainer(
        make_config(steps=steps, seed=seed, on_anomaly="skip",
                    max_anomalies=2, log_every=2, fault_spec=spec), data)
    assert summary["final_step"] < steps, \
        f"budget never halted ({summary['final_step']})"
    count = int(summary["final_metrics"]["anomaly_count"])
    assert count > 2, count
    return (f"halted at step {summary['final_step']} after {count} "
            "anomalies (budget 2)")


def scenario_torn_write(data, seed: int, steps: int) -> str:
    d = tempfile.mkdtemp(prefix="chaos_torn_")
    # the LAST ring write lands torn; earlier ones are whole (no extra
    # end-of-run save happens: the cadence already saved the final step)
    n_saves = steps // 5
    cfg = make_config(steps=steps, seed=seed, ckpt_dir=d, save_steps=5,
                      fault_spec=f"ckpt.write:step={n_saves}:"
                                 "corrupt=truncate")
    run_trainer(cfg, data)
    clean = make_config(steps=steps, seed=seed, ckpt_dir=d, save_steps=5)
    model = get_model("mlp", clean)
    trainer = Trainer(model, clean,
                      {"x": data["train_x"], "y": data["train_y"]},
                      mesh=local_mesh(MESH), process_index=0,
                      num_processes=1)
    with trainer:
        trainer.initialize()
        start = trainer.start_step
    assert 0 < start < steps, (start, steps)
    return f"torn final write; restart fell back to step {start}"


SCENARIOS = {
    "kill_resume": scenario_kill_resume,
    "corrupt_latest": scenario_corrupt_latest,
    "nan_skip": scenario_nan_skip,
    "nan_rollback": scenario_nan_rollback,
    "flaky_io": scenario_flaky_io,
    "budget_halt": scenario_budget_halt,
    "torn_write": scenario_torn_write,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default="all",
                    help="comma-separated scenario names, or 'all': "
                         + ", ".join(SCENARIOS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=20,
                    help="training steps per scenario run (>= 10)")
    args = ap.parse_args(argv)
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    names = (list(SCENARIOS) if args.scenario == "all"
             else [s.strip() for s in args.scenario.split(",") if s.strip()])
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        ap.error(f"unknown scenario(s) {unknown}; have {list(SCENARIOS)}")
    if args.steps < 10:
        ap.error("--steps must be >= 10 (scenarios inject mid-run)")

    data = synthetic_mnist(num_train=640, num_test=64, seed=args.seed)
    failed = 0
    for name in names:
        try:
            detail = SCENARIOS[name](data, args.seed, args.steps)
            print(json.dumps({"scenario": name, "ok": True,
                              "detail": detail}), flush=True)
        except Exception as e:      # a failed invariant is the signal
            failed += 1
            print(json.dumps({"scenario": name, "ok": False,
                              "detail": f"{type(e).__name__}: {e}"}),
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

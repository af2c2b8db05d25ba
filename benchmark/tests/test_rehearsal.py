"""The harness's control flow on the CPU at tiny sizes (``--rehearse 1``):
a run comes out correct and prints no device metric; the control (the
reference in fp8 in the program's place) comes out NOT correct; and a run
whose timed path is broken underneath comes out ``"correct": false``."""

import argparse
import json

import pytest

from benchmark import run as bench_run
from benchmark.manifest import ROOT, Manifest

CELLS = ["gpt2s-train", "bertb-train-s128", "gpt2s-serve-backlog",
         "gpt2s-serve-chat"]


def _run(capsys, cell, seed, env_hook=None, seconds=1.5, trace=0):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace),
                         "--rehearse", "1"], env_hook=env_hook)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_and_prints_no_device_metric(capsys, cell):
    line, out = _run(capsys, cell, 3_000_000_019)
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" not in line["device"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # each number compared is printed beside its limit
    assert sum(1 for ln in out if ln.startswith("compared ")) >= 2


def _env(cell, seed):
    ns = argparse.Namespace(workload=cell, seed=seed, seconds=1.0, trace=0,
                            rehearse=1)
    return bench_run.Env(Manifest(ROOT), ns)


@pytest.mark.parametrize("cell", ["gpt2s-train", "bertb-train-s128",
                                  "gpt2s-serve-chat"])
@pytest.mark.parametrize("seed", [2_200_000_000, 2_200_007_919,
                                  2_200_015_838])
def test_the_control_comes_out_not_correct(capsys, cell, seed):
    env = _env(cell, seed)
    try:
        compared = env.manifest.kind(env.traffic).control(env)
        assert bench_run.decide(env, compared) is False
    finally:
        env.cleanup()


def _frozen_step(trainer=None, **_):
    """A step that returns its state unchanged (metrics still computed)."""
    real = trainer.sync.step

    def step(state, batch):
        import jax
        import jax.numpy as jnp
        keep = jax.tree_util.tree_map(jnp.copy, state)
        new, metrics = real(state, batch)
        return keep.replace(step=new.step), metrics

    trainer.sync.step = step


def _half_batch(trainer=None, **_):
    """A step that leaves out half of the batch (the other half twice)."""
    real = trainer.sync.shard_batch

    def shard(batch):
        half = len(next(iter(batch.values()))) // 2
        return real({k: v[:half].repeat(2, axis=0) for k, v in batch.items()})

    trainer.sync.shard_batch = shard


def _altered_token(server=None, **_):
    """Every decode step's logits favour one token: tokens are altered
    where they are produced."""
    sw = server.engine.sw
    real = sw.decode

    def decode(feats):
        out = dict(real(feats))
        out["logits"] = out["logits"].at[:, 123].add(1e4)
        return out

    sw.decode = decode


@pytest.mark.parametrize("cell,breaker", [
    ("gpt2s-train", _frozen_step), ("bertb-train-s128", _frozen_step),
    ("gpt2s-train", _half_batch), ("gpt2s-serve-backlog", _altered_token),
    ("gpt2s-serve-chat", _altered_token)])
def test_a_broken_timed_path_comes_out_not_correct(capsys, cell, breaker):
    def hook(env):
        env.break_program = breaker
    line, out = _run(capsys, cell, 3_000_000_023, env_hook=hook)
    assert line["correct"] is False, out[-6:]

"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide §2 steps 1-2).

The script itself has no CPU path: run as a script without a TPU it must
refuse. Its phase functions are plain functions of their sizes, so the
rehearsal calls them here at ``gpt_tiny`` widths on the virtual CPU
devices — the control flow, the HTTP round trips, the oracle comparison
and the four-device mesh are the ones the chip run takes; only the sizes
and the expected attention path (no Mosaic kernel off-TPU) differ.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(model="gpt_tiny", seq_len=64, batch_size=8, steps=6,
            expect_custom_call=False)


def test_script_refuses_to_run_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0, out.stdout
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "needs a TPU" in out.stderr
    # refused before any phase: the failure line is all it printed
    assert len(out.stdout.strip().splitlines()) == 1


@pytest.fixture(scope="module")
def trained(tmp_path_factory, cpu8):
    work = str(tmp_path_factory.mktemp("smoke_train"))
    report, run = chip_smoke.train_phase(work, **TINY)
    return report, run


def test_train_phase_at_tiny_widths(trained):
    report, run = trained
    assert report["steps"] == TINY["steps"]
    assert report["loss_last"] < report["loss_first"]
    assert len(report["step_ms"]) >= TINY["steps"] - 1
    assert report["compile_seconds"] > 0
    assert report["flash_custom_calls"] == 0      # no Mosaic off-TPU
    # the CLI built its mesh over every device of the fixture
    assert report["mesh"]["data"] == len(jax.devices())


def test_train_phase_fails_when_the_expected_kernel_is_absent(tmp_path):
    """The chip run's own check, turned on where it cannot hold: the
    phase must raise rather than carry a fallback past."""
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.train_phase(str(tmp_path),
                               **{**TINY, "expect_custom_call": True})


def test_serve_phase_at_tiny_widths(trained, tmp_path):
    _, run = trained
    report = chip_smoke.serve_phase(
        str(tmp_path), run.trainer.model,
        jax.device_get(run.state.params), slots=4, block_size=16,
        prompt_len=16, max_new=16, platforms=("cpu",),
        expect_custom_call=False)
    assert report["requests"] == 7
    assert report["compared"] == 5
    assert report["prefix_cache_hits"] == 1
    assert report["decode_steps"] >= 15
    assert report["decode_attention"] == "xla gather"
    assert (report["first_token_logits_max_abs_diff"]
            <= report["first_token_logits_tolerance"])
    for d in report["divergences"]:
        assert abs(d["logit_gap"]) <= d["tolerance"], d
    assert len(report["smoke_ttft_ms"]) == 5


def test_multichip_phase_on_four_virtual_devices(cpu8, tmp_path):
    report = chip_smoke.multichip_phase(str(tmp_path), devices=cpu8[:4],
                                        **TINY)
    assert report["devices"] == 4
    assert report["arrays_not_on_all_devices"] == []
    assert set(report["per_device_batch_rows"].values()) == {2}
    assert report["all_reduces"] > 0
    assert report["loss_max_rel_diff"] <= report["loss_rtol"]


def test_compilation_cache_helper(monkeypatch):
    """One cache path: the environment's where it names one (and then
    nothing is set in code), else <checkout>/.jax_cache — fixed, inside
    the checkout, never a temp name."""
    from distributed_tensorflow_example_tpu.runtime import device

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x/cache")
        assert device.enable_compilation_cache() == "/x/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = device.enable_compilation_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert device.enable_compilation_cache() == path   # and stays
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

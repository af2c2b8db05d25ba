"""robust_time + median_repeats (bench.py): the artifact-resistant
measurement cores the bench gate rests on. A reading faster than the
roofline is rejected: such an artifact is always absurdly fast, so
robust_time must take the slower pass,
retry on physically impossible or wildly disagreeing readings, and flag
what it cannot fix; the decode row's median_repeats must publish the
median of >=5 repeats (immune to single-call outliers in either
direction), its spread, and a suspect flag when the median itself sits
below the physical floor.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench import median_repeats, robust_time


def _passes(seq):
    it = iter(seq)

    def timed_pass():
        return next(it)
    return timed_pass


def test_takes_slower_of_two_clean_passes():
    dt, suspect = robust_time(_passes([1.0, 1.1]), steps=10)
    assert dt == 1.1 and not suspect


def test_wild_disagreement_retries_then_settles():
    # first pair disagrees 100x (artifact), second pair is clean
    dt, suspect = robust_time(_passes([0.01, 1.0, 1.0, 1.05]), steps=10)
    assert dt == 1.05 and not suspect


def test_wild_disagreement_every_time_is_suspect():
    dt, suspect = robust_time(
        _passes([0.01, 1.0] * 3), steps=10)
    assert suspect and dt == 1.0


def test_impossible_mfu_retries_and_flags():
    # flops/peak chosen so a 0.001s run implies ~10x peak; clean run 0.1s
    kw = dict(steps=10, flops=1e9, peak=1e12, n_dev=1)
    # both passes corrupted every attempt -> suspect
    dt, suspect = robust_time(_passes([0.001, 0.001] * 3), **kw)
    assert suspect
    # corruption clears on the second attempt -> clean
    dt, suspect = robust_time(
        _passes([0.001, 0.001, 0.1, 0.11]), **kw)
    assert dt == pytest.approx(0.11) and not suspect


def test_no_flops_estimate_uses_disagreement_only():
    # identical-but-fast passes can't be flagged without a flops bound:
    # documented limitation — the helper still returns the measurement
    dt, suspect = robust_time(_passes([0.001, 0.001]), steps=10)
    assert dt == pytest.approx(0.001) and not suspect


def test_median_repeats_takes_the_median_and_reports_spread():
    """5 repeats with one slow and one fast outlier: the median is the
    honest middle reading and the spread names the worst deviation."""
    med, spread, suspect = median_repeats(
        _passes([1.0, 0.9, 1.1, 1.02, 0.98]), reps=5)
    assert med == 1.0 and not suspect
    assert spread == pytest.approx(0.1)


def test_median_repeats_shrugs_off_single_fast_artifact():
    """A return-without-running artifact corrupts ONE call: a
    max-of-two estimate wobbles, the median of 5 does not."""
    med, spread, suspect = median_repeats(
        _passes([0.001, 1.0, 1.01, 0.99, 1.0]), reps=5)
    assert med == 1.0 and not suspect
    assert spread == pytest.approx(0.999)   # the outlier IS the spread


def test_median_repeats_floor_retries_then_settles():
    # whole first sample corrupted below the physical floor; the
    # second sample is honest
    med, spread, suspect = median_repeats(
        _passes([0.001] * 3 + [1.0, 1.05, 0.95]), reps=3, floor_s=0.5)
    assert med == 1.0 and not suspect


def test_median_repeats_persistently_impossible_is_suspect():
    med, spread, suspect = median_repeats(
        _passes([0.001] * 9), reps=3, floor_s=0.5, retries=3)
    assert suspect and med == 0.001


def test_median_repeats_validates_reps():
    with pytest.raises(ValueError, match="reps"):
        median_repeats(_passes([1.0]), reps=0)


def test_median_repeats_single_rep_off_tpu_mode():
    # the CPU-sanity config times one repeat with no floor: the value
    # passes through, spread 0, never suspect
    med, spread, suspect = median_repeats(_passes([0.7]), reps=1)
    assert med == 0.7 and spread == 0.0 and not suspect


def test_vs_baseline_excludes_suspect_measurements():
    """A corrupt (suspect-flagged) reading must not move the gate: the
    round-4 incident was a ResNet 'step' of 2.46 ms / 6.28 MFU
    inflating vs_baseline to 1.8x despite robust_time having FLAGGED
    it."""
    import importlib.util, os
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    base = {"mnist_mlp_eps_chip": 100.0, "resnet50_eps_chip": 100.0}
    clean = {"mnist_mlp_eps_chip": 110.0, "resnet50_eps_chip": 110.0}
    assert abs(bench.vs_baseline_geomean(clean, base) - 1.1) < 1e-9
    corrupt = dict(clean, resnet50_eps_chip=5000.0, resnet50_suspect=True)
    # the corrupt 50x reading is excluded; only mnist's 1.1 remains
    assert abs(bench.vs_baseline_geomean(corrupt, base) - 1.1) < 1e-9
    # all-suspect -> neutral 1.0, not a crash
    allbad = {"mnist_mlp_eps_chip": 5000.0, "mnist_mlp_suspect": True}
    assert bench.vs_baseline_geomean(allbad, base) == 1.0


# ---------------------------------------------------------------------------
# decode de-noising (round 6): the two-point device-component fit and
# the gate's preference for it over call-jittered wall-clock
# ---------------------------------------------------------------------------

def test_decode_device_component_fit():
    """Synthetic generation times on the measured model gen_s =
    0.099 + 0.00084*new (BASELINE.md decode roofline): the fit must
    recover the slope (device ms/token) and intercept (call overhead)."""
    from bench import decode_device_component

    t128 = 0.099 + 0.00084 * 128
    t512 = 0.099 + 0.00084 * 512
    dev_ms, overhead_ms = decode_device_component(t128, t512, 128, 512)
    assert dev_ms == pytest.approx(0.84)
    assert overhead_ms == pytest.approx(99.0)


def test_decode_device_component_rejects_bad_lengths():
    from bench import decode_device_component

    with pytest.raises(ValueError, match="new_long > new_short"):
        decode_device_component(0.2, 0.2, 128, 128)


def test_decode_gate_prefers_device_component():
    """Once BOTH baseline and measurement carry the device component,
    the gpt_decode ratio rides it (inverted: ms, lower is faster) and
    per-call jitter in wall-clock tokens/s cannot move the gate; without
    the baseline key the row falls back to wall-clock tokens/s."""
    from bench import vs_baseline_geomean

    base = {"gpt_decode_tokens_s_chip": 5000,
            "gpt_decode_device_token_ms": 0.84}
    # wall-clock halved by a slow call, device component unchanged
    extra = {"gpt_decode_tokens_s_chip": 2500,
             "gpt_decode_device_token_ms": 0.84}
    assert vs_baseline_geomean(extra, base) == pytest.approx(1.0)
    # device component regresses 20% -> the gate sees it
    worse = dict(extra, gpt_decode_device_token_ms=1.05)
    assert vs_baseline_geomean(worse, base) == pytest.approx(0.8)
    # no device baseline yet -> wall-clock fallback (pre-re-base rounds)
    legacy_base = {"gpt_decode_tokens_s_chip": 5000}
    assert vs_baseline_geomean(extra, legacy_base) == pytest.approx(0.5)
    # suspect flag still excludes the row entirely
    sus = dict(extra, gpt_decode_suspect=True,
               gpt_decode_device_token_ms=0.001)
    assert vs_baseline_geomean(sus, base) == 1.0
    # a NEGATIVE slope (corrupt long leg that dodged the suspect flag)
    # must not reach the geomean as a negative ratio (NaN): the row
    # falls back to wall-clock
    neg = dict(extra, gpt_decode_device_token_ms=-0.2)
    assert vs_baseline_geomean(neg, base) == pytest.approx(0.5)

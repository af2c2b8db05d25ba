"""Test fixture: virtual 8-device CPU mesh.

The JAX analogue of the reference's in-process multi-server cluster fixture
``tf.test.create_local_cluster`` (SURVEY.md §4): 8 XLA host devices in one
process give real shardings and real collectives with no TPU pod.

Must run before any jax computation: XLA_FLAGS is read at backend init, and
jax_platforms is forced to cpu so the suite runs the same wherever it is
started. The persistent compilation cache stays off in this process: the
entry points under test turn it on (runtime/device.py
``enable_compilation_cache``), and a test must compile what it tests, not
read back what an earlier run of other code left on disk.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu8():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected >=8 cpu devices, got {len(devs)}"
    return devs


KNOWN_FAILURES_FILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs", "known_failures.txt")


def load_known_failures() -> list[str]:
    """The documented pre-existing tier-1 failure set, one node id per
    line ('#' comments skipped) — THE parser, shared by the drift
    banner below and tests/test_known_failures_guard.py."""
    with open(KNOWN_FAILURES_FILE) as f:
        return [ln.strip() for ln in f
                if ln.strip() and not ln.startswith("#")]


def known_failure_drift(failed: list[str], known: list[str]) -> list[str]:
    """The failed node ids that are NOT documented — with an empty list
    (the state since every pinned test passes on the installed JAX)
    that is every failure."""
    return sorted(set(failed) - set(known))


def build_telemetry_summary() -> str:
    """One-line tier-1 telemetry summary + dead-counter lint. A metric
    name counts as exercised when ANY registry instance of it was ever
    mutated this process (each engine/trainer owns its own registry —
    the accumulator outlives them); a name nothing ever touched is a
    DEAD counter — tests are silent about metrics that exist but are
    never incremented, so this banner is the only place that gap
    shows up. Only namespaced (production) registries contribute, so
    unit-test probe registries can't pollute the line."""
    from distributed_tensorflow_example_tpu.obs.registry import \
        process_metric_names
    names = process_metric_names()
    if not names:
        return ""
    dead = sorted(n for n, touched in names.items() if not touched)
    # per-subsystem breakdown by leading name token (serving_* /
    # predict_* / router_* / training metrics) so a whole subsystem
    # going silent is visible at a glance, not just the global count
    prefixes: dict[str, int] = {}
    for n in names:
        p = n.split("_", 1)[0]
        prefixes[p] = prefixes.get(p, 0) + 1
    by_prefix = " ".join(f"{p}:{c}" for p, c in
                         sorted(prefixes.items()))
    line = (f"TELEMETRY: {len(names)} registry metric(s) seen "
            f"[{by_prefix}], {len(names) - len(dead)} exercised")
    if dead:
        line += (f", {len(dead)} DEAD (registered but never "
                 f"incremented by the suite): {dead}")
    else:
        line += ", 0 dead"
    return line


def build_trace_summary() -> str:
    """One-line tier-1 TRACE summary: spans the suite recorded/dropped
    across every recorder (the always-on flight-recorder ring plus any
    /trace/start captures), and a stitched-export self-check — two
    fabricated per-process exports with a known clock offset must
    stitch into loadable chrome JSON with the offset applied. A
    failure prints as 'stitched-export FAILED' rather than hiding."""
    import json as _json

    from distributed_tensorflow_example_tpu.obs import stitch
    from distributed_tensorflow_example_tpu.obs.trace import \
        process_span_stats
    stats = process_span_stats()
    if not stats["recorded"]:
        return ""
    try:
        exports = [
            {"process": "router", "clock": 10.0,
             "spans": [["router", "req r1", "request", 1.0, 2.0,
                        {"trace_id": "t1"}]]},
            {"process": "replica0", "clock": 110.0,
             "spans": [["replica0", "slot0", "decode", 101.2, 101.8,
                        {"trace_id": "t1"}]]},
        ]
        stitched = stitch.stitch(exports,
                                 offsets={"replica0": 100.0})
        _json.dumps(stitched)
        xs = [e for e in stitched["traceEvents"] if e["ph"] == "X"]
        inner, outer = sorted(xs, key=lambda e: e["dur"])[:2]
        ok = (len(xs) == 2
              and outer["ts"] <= inner["ts"]
              and inner["ts"] + inner["dur"]
              <= outer["ts"] + outer["dur"]
              and len(stitch.summarize_fleet(stitched)["traces"]) == 1)
        check = "stitched-export ok" if ok else "stitched-export FAILED"
    except Exception as e:        # the banner must never mask results
        check = f"stitched-export FAILED ({type(e).__name__})"
    return (f"TRACE: {stats['recorded']} span(s) recorded, "
            f"{stats['dropped']} dropped, {check}")


def build_slo_summary() -> str:
    """One-line tier-1 SLO summary: objectives parse + a pure
    attainment/burn self-check on a fabricated two-sample smoke
    history (10 interactive requests, 8 good — attainment 0.8, burn
    4.0 against a 0.95 goal, breach over equal windows). Prints only
    when the suite actually registered the serving_slo_* counters
    (a serving-flavored run), and a failure prints as FAILED rather
    than hiding. The dead-counter side of the story rides the
    TELEMETRY line: a serving_slo_* name nothing incremented shows
    up there as DEAD."""
    from distributed_tensorflow_example_tpu.obs import slo as obs_slo
    from distributed_tensorflow_example_tpu.obs.registry import (
        Registry, process_metric_names)
    if not any(n.startswith("serving_slo_")
               for n in process_metric_names()):
        return ""
    try:
        objectives = obs_slo.default_objectives() + \
            obs_slo.parse_slo_spec("interactive:hit_rate=0.95")

        def snap(served, good):
            reg = Registry()
            reg.counter("serving_slo_served_interactive_total").inc(
                served)
            reg.counter("serving_slo_good_interactive_total").inc(
                good)
            return reg.snapshot()

        hist = [(0.0, snap(0, 0)), (60.0, snap(10, 8))]
        res = obs_slo.evaluate(
            hist, [o for o in objectives
                   if o.key() == "interactive:hit_rate"
                   and o.goal == 0.95],
            fast_s=60.0, slow_s=60.0, threshold=2.0)
        r = res[0]
        ok = (r["attainment"] == 0.8
              and abs(r["burn_fast"] - 4.0) < 1e-9 and r["breach"])
        check = ("attainment self-check ok (0.8 @ goal 0.95 -> "
                 "burn 4.0, breach)" if ok
                 else f"attainment self-check FAILED ({r})")
        return (f"SLO: {len(objectives)} objective(s) loaded "
                f"({len(obs_slo.default_objectives())} default + "
                f"spec), {check}")
    except Exception as e:      # the banner must never mask results
        return f"SLO: self-check FAILED ({type(e).__name__}: {e})"


def build_graftlint_summary() -> str:
    """One-line graftlint summary for the tier-1 banner: rule count,
    finding count (tier-1 requires 0 — tests/test_graftlint.py is the
    enforcing test; this line is the at-a-glance view), suppression
    count (pinned by docs/graftlint_suppressions.txt — growth without
    documentation fails the drift guard), and the baseline size
    (guarded to stay 0). Pure-stdlib AST analysis, so the banner adds
    no jax work to the run."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from tools.graftlint import lint_paths
    return lint_paths().summary_line()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Known-failure-set drift banner + tier-1 telemetry/lint summary.

    Drift: tier-1 carries a documented pre-existing failure set
    (docs/known_failures.txt); any failure NOT on that list is flagged
    here by name so a fresh regression can never hide inside the
    known-bad count (see tests/test_known_failures_guard.py for the
    companion re-run guard). Print-only — the run's exit status
    already reflects the failures themselves.

    Telemetry: one line naming registry metrics the whole suite never
    incremented (the dead-counter lint — see
    ``build_telemetry_summary``), and one graftlint line (static
    invariant rules + suppression inventory — the static complement of
    the dead-counter lint; see ``build_graftlint_summary``)."""
    try:
        tele = build_telemetry_summary()
    except Exception:           # the lint must never mask test results
        tele = ""
    try:
        trace = build_trace_summary()
    except Exception:
        trace = ""
    try:
        slo = build_slo_summary()
    except Exception:
        slo = ""
    try:
        lint = build_graftlint_summary()
    except Exception:
        lint = ""
    if tele or trace or slo or lint:
        terminalreporter.section("TIER-1 TELEMETRY", sep="-")
        if tele:
            terminalreporter.line(tele)
        if trace:
            terminalreporter.line(trace)
        if slo:
            terminalreporter.line(slo)
        if lint:
            terminalreporter.line(lint)
    failed = [r.nodeid for r in terminalreporter.stats.get("failed", [])]
    if not failed:
        return
    try:
        drift = known_failure_drift(failed, load_known_failures())
    except OSError:
        return
    if drift:
        terminalreporter.section("KNOWN-FAILURE-SET DRIFT",
                                 sep="=", red=True, bold=True)
        terminalreporter.line(
            f"{len(drift)} failed test(s) NOT on the documented "
            "pre-existing list (docs/known_failures.txt) — these are "
            "NEW regressions, not sandbox noise:")
        for n in drift:
            terminalreporter.line(f"  {n}")

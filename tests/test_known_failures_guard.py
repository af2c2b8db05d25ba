"""Guard on the documented pre-existing failure set.

docs/known_failures.txt names the tier-1 tests that are known to fail in
this environment. It is EMPTY since the eleven tests once pinned there
pass on the installed JAX (0.9.0), and it may only stay that way or name
a failure that was diagnosed. The raw failure COUNT is what gets
eyeballed, which leaves a hole: a new regression plus a
coincidentally-fixed old failure keeps the count flat while the SET
drifts. Two guards close it:

- this module re-runs whatever the list names BY NAME in one fresh
  pytest process and asserts every listed test (a) still exists and (b)
  still fails — a listed test that starts passing means the list is
  stale and must shrink, loudly, in the same PR that fixed it. With an
  empty list there is nothing to re-run and the guard holds;
- the conftest ``pytest_terminal_summary`` hook prints a
  ``KNOWN-FAILURE-SET DRIFT`` banner whenever a tier-1 run fails a
  test that is NOT on the list — with an empty list, every failure.

The same conftest banner path also prints a one-line TIER-1 TELEMETRY
summary with a dead-counter lint: an obs-registry metric every test in
the suite left untouched is named there — tests are silent about
counters that exist but are never incremented, so the banner is where
that rot becomes visible (see ``conftest.build_telemetry_summary``).
"""

import os
import subprocess
import sys

from conftest import (build_telemetry_summary, known_failure_drift,
                      load_known_failures)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_telemetry_summary_counts_dead_metrics():
    """The dead-counter lint sees every registry in the process and
    names exactly the metrics nothing ever mutated — exercised-
    anywhere wins over dead-somewhere (each engine registers its own
    copy of a name)."""
    from distributed_tensorflow_example_tpu.obs.registry import Registry
    r1 = Registry(namespace="lintprobe")
    r2 = Registry(namespace="lintprobe")
    r1.counter("lint_probe_dead_total")
    r1.counter("lint_probe_live_total").inc()
    # same name dead in r2 but touched in r1 -> exercised overall
    r2.counter("lint_probe_live_total")
    # un-namespaced registries are test scaffolding: never in the line
    Registry().counter("lint_probe_scaffold_total")
    line = build_telemetry_summary()
    assert line.startswith("TELEMETRY: ")
    assert "lint_probe_dead_total" in line
    assert "lint_probe_live_total" not in line
    assert "lint_probe_scaffold_total" not in line
    r1.counter("lint_probe_dead_total").inc()       # now exercised
    assert "lint_probe_dead_total" not in build_telemetry_summary()


def test_drift_is_every_failure_off_the_list():
    """The banner's set arithmetic: an undocumented failure is drift —
    with the list empty, every failure is."""
    failed = ["tests/test_a.py::test_x", "tests/test_b.py::test_y[1]"]
    assert known_failure_drift(failed, []) == failed
    assert known_failure_drift(failed, failed[:1]) == failed[1:]
    assert known_failure_drift([], failed) == []


def test_known_failure_set_is_stable():
    known = load_known_failures()
    if not known:
        return      # nothing pinned: nothing may fail, the banner's job
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "--tb=no", *known],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    tail = out.stdout[-3000:] + out.stderr[-1500:]
    # rc 1 = tests ran and failed (expected); anything else is a
    # collection/usage error — e.g. a documented id was renamed away,
    # which would silently shrink the guard's coverage
    assert out.returncode == 1, (
        f"guard subprocess rc={out.returncode} (collection error? a "
        f"documented node id no longer exists?):\n{tail}")
    failed = {ln.split(" ")[1] for ln in out.stdout.splitlines()
              if ln.startswith("FAILED ")}
    passed_again = set(known) - failed
    assert not passed_again, (
        "tests on the documented known-failure list PASSED — the list "
        f"is stale; remove them from docs/known_failures.txt in this "
        f"PR: {sorted(passed_again)}\n{tail}")
    unexpected = failed - set(known)
    assert not unexpected, (
        f"guard subprocess failed undocumented tests: "
        f"{sorted(unexpected)}\n{tail}")

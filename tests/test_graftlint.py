"""graftlint: the invariant-checking static-analysis suite + the THR01
runtime thread-ownership sanitizer.

Three layers of coverage:

- **rule fixtures** — for every rule, at least one synthetic TRUE
  POSITIVE (the contract violation fires) and one FALSE-POSITIVE GUARD
  (the documented escape hatch / legal twin does NOT fire), so a rule
  edit that silently widens or narrows a rule fails here first;
- **repo gates** — the whole-package lint must run CLEAN (this is the
  tier-1 registration of ``python -m tools.graftlint``), the
  suppression inventory must match docs/graftlint_suppressions.txt
  EXACTLY (the drift guard: a growing suppression count fails loudly,
  same pattern as the known-failure-set guard), and the baseline must
  stay empty;
- **THR01 runtime** — the ``thread_sanitizer=True`` debug engine
  serves legal traffic byte- and dispatch-identically to the plain
  engine, and a seeded cross-thread touch of a scheduler-owned field
  raises :class:`ThreadOwnershipError` naming the field and thread.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.graftlint import (ALL_RULES, lint_paths, lint_source,
                             lint_sources, load_documented_suppressions,
                             load_files, suppression_inventory)
from tools.graftlint import engine as lint_engine


def names(result, rule=None):
    """Finding rule names (optionally filtered) — the assertion helper."""
    return [f.rule for f in result.findings
            if rule is None or f.rule == rule]


# ---------------------------------------------------------------------------
# JIT01 — host sync / impurity inside jit-reachable code
# ---------------------------------------------------------------------------

def test_jit01_true_positives():
    src = """
import time
import numpy as np
import jax

@jax.jit
def step(state, batch):
    t = time.perf_counter()          # wall clock under trace
    host = np.asarray(state)         # host materialization
    flag = float(batch)              # concretizes a traced arg
    return state.item()              # device->host sync
"""
    r = lint_source(src, rules=["JIT01"])
    msgs = " | ".join(f.message for f in r.findings)
    assert len(r.findings) == 4, msgs
    assert "wall clock" in msgs and "item()" in msgs
    assert "np.asarray" in msgs and "float(batch)" in msgs


def test_jit01_reaches_through_helpers_and_scan_bodies():
    """Reachability is the rule's teeth: a helper called from a jitted
    function and a lax.scan body are both jit-reachable."""
    src = """
import jax
from jax import lax

def _helper(x):
    return x.item()

@jax.jit
def step(x):
    return _helper(x)

def body(carry, x):
    counter.inc()
    return carry, x

def outer(xs):
    return lax.scan(body, 0, xs)
"""
    r = lint_source(src, rules=["JIT01"])
    assert sorted(f.symbol for f in r.findings) == ["_helper", "body"]


def test_jit01_false_positive_guards():
    """The documented escape hatches must NOT fire: host callbacks run
    on the host by design, static-annotated scalars are shape math,
    .at[].set() is the functional array update, and un-jit-reachable
    code is free to sync."""
    src = """
import jax

@jax.jit
def step(x, capacity: int):
    jax.experimental.io_callback(lambda v: print(v.item()), None, x)
    scale = float(capacity)              # annotated static scalar
    return x.at[0].set(scale)            # functional update

def driver(x):                           # never traced: host code
    import time
    t = time.time()
    return x.item(), t
"""
    r = lint_source(src, rules=["JIT01"])
    assert r.findings == [], [f.render() for f in r.findings]


def test_jit01_lambda_body_direct_call():
    """Regression: a lambda whose BODY is itself the offending call
    (`lambda y: y.item()`) must fire — _scan walks child nodes, so the
    body-expression root needs its own check."""
    src = """
import jax

@jax.jit
def step(x):
    f = lambda y: y.item()
    return f(x)
"""
    r = lint_source(src, rules=["JIT01"])
    assert len(r.findings) == 1, [f.render() for f in r.findings]
    assert "item()" in r.findings[0].message


# ---------------------------------------------------------------------------
# DON01 — jitted train-step wrappers declare donation
# ---------------------------------------------------------------------------

def test_don01_true_positives():
    src = """
import jax

@jax.jit
def train_step(state, batch):
    return state

def build(step_fn):
    return jax.jit(step_fn)
"""
    r = lint_source(src, rules=["DON01"])
    assert names(r) == ["DON01", "DON01"]
    assert "donate" in r.findings[0].message


def test_don01_false_positive_guards():
    """Declared donation passes (an empty tuple too — explicit is the
    contract), and jit of a non-step function is out of scope."""
    src = """
import functools
import jax

@functools.partial(jax.jit, donate_argnums=(0,))
def train_step(state, batch):
    return state

@functools.partial(jax.jit, donate_argnums=())
def eval_step(state, batch):
    return state

def build(render_fn):
    return jax.jit(render_fn)        # not step-like: no contract
"""
    r = lint_source(src, rules=["DON01"])
    assert r.findings == [], [f.render() for f in r.findings]


# ---------------------------------------------------------------------------
# THR01 — scheduler-owned fields vs thread-marked methods (static face)
# ---------------------------------------------------------------------------

_THR_SRC = """
@scheduler_owned("_live", "_pool")
class Engine:
    def __init__(self):
        self._live = {}
        self._pool = None

    @scheduler_thread
    def _admit(self):
        self._live[0] = object()     # owner thread: full access

    @snapshot_view
    def stats(self):
        return len(self._live)       # view: reads allowed

    @snapshot_view
    def bad_view(self):
        self._live = {}              # view WRITING: violation

    @snapshot_view
    def bad_clear(self):
        self._live.clear()           # mutator CALL keeps ctx=Load:
                                     # still a write — violation

    @snapshot_view
    def bad_item(self):
        self._live[0] = object()     # item write through the view

    @snapshot_view
    def bad_through(self):
        self._pool.head = None       # attribute write-through

    @snapshot_view
    def ok_reads(self):
        self._live.get(0)            # non-mutating call: legal
        return self._pool.estimate(1)

    def submit(self):
        return self._pool            # unmarked method: violation

    def helper(self):
        return self.max_queue        # unowned field: free
"""


def test_thr01_true_positives():
    r = lint_source(_THR_SRC, rules=["THR01"])
    by_sym = {f.symbol: f.message for f in r.findings}
    assert set(by_sym) == {"Engine.bad_view", "Engine.bad_clear",
                           "Engine.bad_item", "Engine.bad_through",
                           "Engine.submit"}
    assert "writes scheduler-owned field `_live`" in by_sym["Engine.bad_view"]
    assert "mutating call `.clear()`" in by_sym["Engine.bad_clear"]
    assert "item assignment" in by_sym["Engine.bad_item"]
    assert "write through `.head`" in by_sym["Engine.bad_through"]
    assert "`_pool`" in by_sym["Engine.submit"]


def test_thr01_false_positive_guards():
    """__init__, @scheduler_thread access, @snapshot_view reads
    (including non-mutating method calls like ``.get()``/``.estimate()``),
    and unowned fields are all legal — zero findings besides the seeded
    violations."""
    r = lint_source(_THR_SRC, rules=["THR01"])
    legal = {"Engine.__init__", "Engine._admit", "Engine.stats",
             "Engine.ok_reads", "Engine.helper"}
    assert not legal & {f.symbol for f in r.findings}


# ---------------------------------------------------------------------------
# OBS01 — metric-name literals resolve to a registered metric
# ---------------------------------------------------------------------------

def test_obs01_true_positive_and_guards():
    src = '''
class Engine:
    def __init__(self, reg):
        self._c = reg.counter("serving_requests_total", "requests")
        self._g = reg.gauge("serving_queue_depth", "queue depth")

    def stats(self, snap):
        """serving_commentary_total in prose must not fire."""
        return {
            "done": snap["serving_requests_total"]["value"],   # ok
            "typo": snap["serving_requestz_total"]["value"],   # TYPO
            "data": snap.get("train_batch"),       # not metric-shaped
        }
'''
    r = lint_source(src, rules=["OBS01"])
    assert len(r.findings) == 1, [f.render() for f in r.findings]
    assert "serving_requestz_total" in r.findings[0].message


def test_obs01_silent_without_registrations():
    """No counter()/gauge() universe in scope -> the rule cannot
    calibrate what a metric name looks like, so it stays silent
    instead of guessing."""
    src = 'X = {"serving_requests_total": 1}\n'
    assert lint_source(src, rules=["OBS01"]).findings == []


def test_obs01_bare_string_statement_is_prose():
    """Regression: a bare ONE-TOKEN string statement — exactly
    metric-shaped, unregistered — is prose, not a metric reference.
    The docstring exemption must hold even though ast.walk still
    visits the Constant inside the exempted ast.Expr."""
    src = '''
class E:
    def __init__(self, reg):
        self._c = reg.counter("serving_requests_total", "requests")

    def stats(self):
        "serving_requestz_total"
        return {}
'''
    r = lint_source(src, rules=["OBS01"])
    assert r.findings == [], [f.render() for f in r.findings]


# ---------------------------------------------------------------------------
# TRC01 — span-name literals must resolve against docs/span_names.txt
# ---------------------------------------------------------------------------

def test_trc01_true_positive_and_guards():
    """A span() literal NOT in docs/span_names.txt is a finding; a
    declared one is clean; attribute calls like a regex match's
    .span(1) and variable span names are out of scope (heuristic,
    documented)."""
    src = '''
from distributed_tensorflow_example_tpu.obs.trace import add_span, span

def work(m, name):
    with span("prefill", lane="slot0"):        # declared: clean
        pass
    with span("prefil", lane="slot0"):         # TYPO: finding
        pass
    add_span("queue_wait", 0.0, 1.0)           # declared: clean
    with span(name):                           # variable: skipped
        pass
    return m.span(1)                           # regex match: skipped
'''
    r = lint_source(src, rules=["TRC01"])
    assert len(r.findings) == 1, [f.render() for f in r.findings]
    assert "'prefil'" in r.findings[0].message


def test_trc01_sees_span_name_kwarg_default_and_rspan():
    """The engine's ``span_name`` parameter defaults / keyword
    arguments and the router's ``_rspan`` wrapper are span-recording
    entry points too — their literals must resolve."""
    src = '''
from distributed_tensorflow_example_tpu.obs.trace import span

def _dispatch(feats, span_name: str = "decode_step"):
    with span(span_name):
        pass

def caller(self, ctx, rid):
    _dispatch({}, span_name="verify_stepz")     # TYPO: finding
    self._rspan(ctx, rid, "hedgge", 0.0, 1.0)   # TYPO: finding
    self._rspan(ctx, rid, "hedge", 0.0, 1.0)    # declared: clean
'''
    r = lint_source(src, rules=["TRC01"])
    flagged = {f.message.split("'")[1] for f in r.findings}
    assert flagged == {"verify_stepz", "hedgge"}, (
        [f.render() for f in r.findings])


def test_trc01_span_inventory_drift_guard():
    """docs/span_names.txt is pinned BOTH ways (the known_failures.txt
    pattern): every statically-visible span-name literal in the lint
    surface must be declared (TRC01 enforces that side on every run),
    and every declared name must still be USED somewhere — a stale
    inventory line is as loud as an undeclared span."""
    from tools.graftlint import load_files
    from tools.graftlint.rules import (collect_span_literals,
                                       load_span_inventory)
    files, errors = load_files()
    assert not errors
    used = set(collect_span_literals(files))
    declared = load_span_inventory()
    assert used == declared, (
        f"span inventory drift — undeclared: {sorted(used - declared)}"
        f", stale: {sorted(declared - used)} (update "
        "docs/span_names.txt alongside the span() call sites)")


# ---------------------------------------------------------------------------
# CFG01 — declared-but-never-read config fields / CLI flags
# ---------------------------------------------------------------------------

def test_cfg01_true_positive_and_guards():
    cfg = """
import dataclasses

@dataclasses.dataclass
class TrainConfig:
    live_knob: int = 1
    dead_knob: int = 0
"""
    cli = """
def use(cfg):
    return cfg.live_knob

def build(ap):
    ap.add_argument("--wired", type=int)
    ap.add_argument("--ghost-flag", type=int)

def read(args):
    return args.wired
"""
    r = lint_sources({"pkg/config.py": cfg, "pkg/cli.py": cli},
                     rules=["CFG01"])
    flagged = {f.message.split("(")[1].split(")")[0] for f in r.findings}
    assert flagged == {"'dead_knob'", "'ghost_flag'"}, (
        [f.render() for f in r.findings])


def test_cfg01_getattr_counts_as_a_read():
    cfg = ("import dataclasses\n\n@dataclasses.dataclass\n"
           "class C:\n    probed: int = 0\n")
    use = "def f(c):\n    return getattr(c, 'probed', None)\n"
    r = lint_sources({"a/config.py": cfg, "a/use.py": use},
                     rules=["CFG01"])
    assert r.findings == []


# ---------------------------------------------------------------------------
# engine mechanics: suppressions, baseline budget, parse errors
# ---------------------------------------------------------------------------

def test_suppression_comment_suppresses_exactly_its_rule():
    src = """
import jax

@jax.jit
def step(x):
    a = x.item()      # graftlint: disable=JIT01  (fixture)
    return x.item()
"""
    r = lint_source(src, rules=["JIT01"])
    assert len(r.findings) == 1 and len(r.suppressed) == 1
    # the wrong rule name does NOT suppress
    r2 = lint_source(src.replace("disable=JIT01", "disable=DON01"),
                     rules=["JIT01"])
    assert len(r2.findings) == 2 and not r2.suppressed


def test_baseline_entry_excuses_at_most_one_finding():
    src = ("import jax\n\n@jax.jit\ndef step(x):\n"
           "    a = x.item()\n    return x.item()\n")
    sf = lint_engine.SourceFile.from_source(src, "fix.py")
    full = lint_engine.lint_files([sf], rules=["JIT01"])
    assert len(full.findings) == 2
    entry = full.findings[0].as_dict()
    r = lint_engine.lint_files([sf], rules=["JIT01"], baseline=[entry])
    assert len(r.findings) == 1 and len(r.baselined) == 1


def test_parse_error_is_loud_and_unknown_rule_raises():
    files, errors = [], []
    try:
        lint_engine.SourceFile.from_source("def broken(:\n", "bad.py")
    except SyntaxError:
        errors.append("raised")
    assert errors == ["raised"]
    with pytest.raises(ValueError, match="unknown rule"):
        lint_source("x = 1\n", rules=["NOPE99"])


# ---------------------------------------------------------------------------
# repo gates — the tier-1 registration of the lint itself
# ---------------------------------------------------------------------------

def test_repo_lint_runs_clean():
    """THE tier-1 gate: all 5 rules over the full package +
    experiments, zero findings. A new contract violation anywhere in
    the lint surface fails HERE, with the finding rendered."""
    r = lint_paths()
    assert len(r.rule_names) >= 5
    assert r.files >= 70, f"lint surface shrank to {r.files} files"
    assert r.clean, "\n".join(
        f.render() for f in r.parse_errors + r.findings)


def test_changed_mode_narrows_reporting_not_analysis():
    full = lint_paths()
    chg = lint_paths(changed=True)
    assert chg.rule_names == full.rule_names
    assert chg.files == full.files          # analysis surface identical
    assert set(f.fingerprint() for f in chg.findings) <= set(
        f.fingerprint() for f in full.findings)


def test_changed_mode_git_failure_is_loud(monkeypatch):
    """Regression: a git failure under --changed must raise, not
    return an empty scope — an empty scope filters every finding and
    reports a bogus clean run."""
    def boom(*a, **k):
        raise OSError("no git binary")
    monkeypatch.setattr(lint_engine.subprocess, "run", boom)
    with pytest.raises(OSError, match="--changed needs git"):
        lint_engine.changed_py_files()


def test_changed_scope_normalizes_root_spellings(monkeypatch):
    """Regression: git emits normalized repo-relative names
    ('experiments/x.py'), so a './experiments' (or trailing-slash)
    root spelling must reach the same scope — an unnormalized prefix
    would silently filter every finding into a bogus clean run."""
    class _Out:
        returncode = 0
        stdout = "experiments/x.py\nsomewhere/else.py\n"
        stderr = ""
    monkeypatch.setattr(lint_engine.subprocess, "run",
                        lambda *a, **k: _Out())
    want = {"experiments/x.py"}
    assert lint_engine.changed_py_files(("experiments",)) == want
    assert lint_engine.changed_py_files(("./experiments",)) == want
    assert lint_engine.changed_py_files(("experiments/",)) == want


def test_missing_lint_root_is_loud():
    """Regression: a typo'd path must raise (CLI exit 2), not report
    '0 file(s), 0 finding(s)' — a green lint that analyzed nothing."""
    with pytest.raises(ValueError, match="does not exist"):
        lint_engine.iter_py_files(("no_such_dir_graftlint",))


def test_cli_json_contract():
    """`python -m tools.graftlint --json` exits 0 on the clean tree
    with the machine-readable shape bench/CI consume."""
    import json
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["clean"] is True and payload["findings"] == []
    assert len(payload["rules"]) >= 5
    # CLI must agree with the library run of the same surface (NOT with
    # the comment inventory: a documented comment whose finding was
    # fixed legitimately suppresses nothing — that's the drift guard's
    # business, not the JSON contract's)
    assert payload["suppressed"] == len(lint_engine.lint_paths().suppressed)


def test_suppression_inventory_drift_guard():
    """A suppression added (or removed) without updating
    docs/graftlint_suppressions.txt fails loudly — the same
    stale-list protection the known-failure guard gives the failure
    set. Suppressions live next to the code they excuse; this pin
    makes their growth a reviewed event instead of a drift."""
    files, _ = load_files()
    actual = suppression_inventory(files)
    documented = load_documented_suppressions()
    undocumented = {k: v for k, v in actual.items()
                    if documented.get(k) != v}
    stale = {k: v for k, v in documented.items() if k not in actual}
    assert not undocumented and not stale, (
        "suppression inventory drifted — update "
        "docs/graftlint_suppressions.txt in the same PR.\n"
        f"in tree but not documented (or count changed): {undocumented}\n"
        f"documented but gone from the tree: {stale}")


def test_baseline_is_pinned_empty():
    """The baseline exists for emergencies only; debt goes in
    commented suppressions, which the drift guard above reviews."""
    assert lint_engine.load_baseline() == [], (
        "tools/graftlint/baseline.json grew — move entries to "
        "commented `# graftlint: disable=` suppressions (documented "
        "in docs/graftlint_suppressions.txt) or fix the findings")


def test_every_rule_name_documented_in_design():
    with open(os.path.join(ROOT, "docs", "DESIGN.md")) as f:
        design = f.read()
    for rule in ALL_RULES:
        assert rule.name in design, (
            f"rule {rule.name} missing from DESIGN.md §16")


# ---------------------------------------------------------------------------
# THR01 runtime sanitizer — the dynamic complement
# ---------------------------------------------------------------------------

PROMPT_LEN, MAX_NEW, SLOTS = 8, 4, 2


@pytest.fixture(scope="module")
def stepwise_dir(tmp_path_factory):
    import jax
    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.serving import export_generator

    d = str(tmp_path_factory.mktemp("tsan"))
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    params = m.init(jax.random.key(0))
    export_generator(m, params, d, prompt_len=PROMPT_LEN,
                     max_new_tokens=MAX_NEW, batch_size=1, ragged=True,
                     stepwise=True, slots=SLOTS, platforms=("cpu",))
    return d


def _prompts(n, seed=7):
    import numpy as np
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 1000, (int(rs.randint(1, PROMPT_LEN + 1)),)
                       ).astype(np.int32) for _ in range(n)]


def _run(export_dir, prompts, **engine_kw):
    from distributed_tensorflow_example_tpu.serving import load_stepwise
    from distributed_tensorflow_example_tpu.serving_batch import \
        GenerationEngine

    eng = GenerationEngine(load_stepwise(export_dir), **engine_kw)
    futs = [eng.submit(p) for p in prompts]
    eng.start()
    try:
        got = [f.result(timeout=120) for f in futs]
        counters = (eng.prefills, eng.decode_steps, eng.tokens_out)
    finally:
        eng.close()
    return got, counters


def test_sanitizer_armed_engine_is_byte_and_dispatch_identical(
        stepwise_dir):
    """The sanitizer is observation, not behavior: the armed engine's
    outputs AND dispatch counters match the plain engine exactly (so
    the disabled default provably adds zero dispatches either way)."""
    prompts = _prompts(SLOTS * 2)
    plain, c_plain = _run(stepwise_dir, prompts)
    armed, c_armed = _run(stepwise_dir, prompts, thread_sanitizer=True)
    assert armed == plain
    assert c_armed == c_plain


def test_sanitizer_catches_seeded_cross_thread_mutation(stepwise_dir):
    """The acceptance probe: after the scheduler thread takes
    ownership, a foreign-thread touch of a scheduler-owned field
    raises, and the error NAMES the offending field and thread."""
    import threading

    from distributed_tensorflow_example_tpu.serving import load_stepwise
    from distributed_tensorflow_example_tpu.serving_batch import (
        GenerationEngine, ThreadOwnershipError)

    eng = GenerationEngine(load_stepwise(stepwise_dir),
                           thread_sanitizer=True).start()
    try:
        # legal traffic first: the armed engine serves it clean
        assert len(eng.generate(_prompts(1)[0])) == MAX_NEW
        with pytest.raises(ThreadOwnershipError) as read_err:
            eng._live            # noqa: B018 — the seeded violation
        msg = str(read_err.value)
        assert "_live" in msg, msg
        assert threading.current_thread().name in msg, msg
        assert "scheduler" in msg
        with pytest.raises(ThreadOwnershipError) as write_err:
            eng._free = []
        assert "_free` write" in str(write_err.value)
        # snapshot views stay legal from this same foreign thread
        # while the scheduler thread is live
        assert eng.stats()["requests_done"] == 1
    finally:
        eng.close()
    # post-join teardown reverted ownership: access is free again
    assert eng._live == {}


def test_sanitizer_disabled_keeps_the_plain_class(stepwise_dir):
    """Off = not even a branch on the attribute path: the instance
    keeps its plain class and plain dict attributes."""
    from distributed_tensorflow_example_tpu.serving import load_stepwise
    from distributed_tensorflow_example_tpu.serving_batch import \
        GenerationEngine

    eng = GenerationEngine(load_stepwise(stepwise_dir))
    try:
        assert type(eng) is GenerationEngine
        assert "_live" in eng.__dict__          # plain attribute
        assert not eng.thread_sanitizer
    finally:
        eng.close()


def test_close_keeps_sanitizer_armed_when_join_times_out(stepwise_dir):
    """Regression: a timed-out join means the scheduler thread is
    STILL RUNNING. Round 14 tightened the contract: close() now raises
    EngineStalledError BEFORE its teardown touches any scheduler-owned
    state (rounds 9–13 let the teardown run and relied on the armed
    sanitizer to catch close's own race) — and the sanitizer stays
    armed past the raise, so a later foreign-thread touch of `_live`
    still trips ThreadOwnershipError."""
    import threading

    from distributed_tensorflow_example_tpu.serving import load_stepwise
    from distributed_tensorflow_example_tpu.serving_batch import (
        EngineStalledError, GenerationEngine, ThreadOwnershipError)

    eng = GenerationEngine(load_stepwise(stepwise_dir),
                           thread_sanitizer=True)
    foreign_tid = threading.get_ident() + 1

    class _StuckThread:
        def join(self, timeout=None):
            pass

        def is_alive(self):
            return True

    eng._san_tid = foreign_tid          # scheduler "owns" and is live
    eng._thread = _StuckThread()
    with pytest.raises(EngineStalledError, match="heartbeat"):
        eng.close(timeout=0.01)
    assert eng._san_tid == foreign_tid  # still armed
    with pytest.raises(ThreadOwnershipError, match="_live"):
        eng._live                       # noqa: B018 — the armed probe


def test_http_server_rejects_sanitizer_without_engine(stepwise_dir):
    """Regression: thread_sanitizer=True on a server that would run
    the unguarded path (scheduler off / predict artifact) must raise,
    not silently serve unsanitized."""
    from distributed_tensorflow_example_tpu.serving_http import \
        PredictServer

    with pytest.raises(ValueError, match="thread_sanitizer"):
        PredictServer(stepwise_dir, scheduler="off",
                      thread_sanitizer=True)


def test_ownership_markers_are_declared_metadata():
    """The static rule and the runtime sanitizer read the SAME
    declaration: @scheduler_owned on the class, @scheduler_thread /
    @snapshot_view on the methods."""
    from distributed_tensorflow_example_tpu.serving_batch import \
        GenerationEngine as GE

    owned = set(GE.__scheduler_owned__)
    assert {"_live", "_pool", "cache"} <= owned
    assert GE._admit.__scheduler_thread__
    assert GE._shared_step.__scheduler_thread__
    assert GE.stats.__snapshot_view__
    assert GE.metrics_snapshot.__snapshot_view__

"""The graftlint rule set — each rule encodes one existing repo contract.

=====  ====================================================================
rule   contract it machine-checks
=====  ====================================================================
JIT01  jit purity: no host syncs / wall clocks / metrics mutation inside
       jit-reachable code (the async-dispatch training loop and the
       compiled decode step both die by a thousand ``.item()`` cuts).
       An escape hatch exists: arguments of ``io_callback`` /
       ``pure_callback`` / ``jax.debug.callback`` run ON the host by
       design and are never flagged.
DON01  jitted train-step wrappers must DECLARE donation
       (``donate_argnums``/``donate_argnames``) — the static face of the
       tests/test_donation.py contract (~+1.3 GiB bert_long peak when
       donation is silently lost).
THR01  fields named by a ``@scheduler_owned(...)`` class marker may only
       be referenced from ``@scheduler_thread`` methods (full access),
       ``@snapshot_view`` methods (reads only — mutator calls like
       ``.clear()``, item writes, and attribute write-throughs count as
       writes), or ``__init__`` — the single-flight scheduler
       discipline of serving_batch.py, statically.
OBS01  every metric-name string literal must resolve to a registered
       ``counter()``/``gauge()``/``histogram()`` — the static inverse of
       the tier-1 dead-counter lint: that one catches registered-but-
       never-touched, this one catches a TYPO'D name (e.g. in a
       snapshot lookup) the runtime lint structurally cannot see.
TRC01  every span-name literal passed to ``span()``/``add_span()``
       must resolve against the declared span-name inventory
       (``docs/span_names.txt``, drift-guarded by
       tests/test_graftlint.py the way known_failures.txt is) — the
       fleet stitcher and the trace summaries group lanes by span
       NAME, so a typo'd name silently drops a span from every
       grouped view; OBS01's sibling for the trace vocabulary.
CFG01  config dataclass fields (config.py) and argparse ``--flags``
       declared but never read anywhere — a silently ignored knob is
       worse than an error (the repo's own config-validation mantra).
=====  ====================================================================

Every rule is heuristic where Python demands it (documented inline);
precision losses resolve through ``# graftlint: disable=RULE`` with a
comment, never by weakening the rule silently.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterable, Sequence

from .engine import REPO_ROOT, Finding, SourceFile

# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def dotted(node: ast.AST) -> str | None:
    """'jax.jit' for Attribute chains / Names; None for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last(name: str | None) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


def _tokens(name: str) -> list[str]:
    return [t for t in name.split("_") if t]


def identifiers(node: ast.AST) -> set[str]:
    """Every Name id and Attribute attr inside an expression — the
    coarse 'which functions might this expression reference' set."""
    out: set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
    return out


def decorator_names(fn: ast.FunctionDef | ast.AsyncFunctionDef
                    ) -> set[str]:
    """Last-segment names of a def's decorators; for ``@partial(f, ...)``
    decorators the partial's first argument counts too."""
    out: set[str] = set()
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call):
            name = _last(dotted(dec.func))
            out.add(name)
            if name == "partial" and dec.args:
                out.add(_last(dotted(dec.args[0])))
        else:
            out.add(_last(dotted(dec)))
    return out


def collect_aliases(tree: ast.Module) -> dict[str, set[str]]:
    """One-level local aliases: each single-target Assign maps the bound
    name to the identifiers of its RHS (``step_fn = self._auto_step``,
    ``f = a if cond else b``) — so ``jit(step_fn)`` still finds the def.
    Shared by JIT01 (reachability roots) and DON01 (call-site form)."""
    aliases: dict[str, set[str]] = {}
    for n in ast.walk(tree):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.targets[0], ast.Name):
            aliases.setdefault(n.targets[0].id,
                               set()).update(identifiers(n.value))
    return aliases


def expand_aliases(names: set[str],
                   aliases: dict[str, set[str]]) -> set[str]:
    """Fixpoint-expand ``names`` through :func:`collect_aliases`' map."""
    seen, frontier = set(names), set(names)
    while frontier:
        nxt: set[str] = set()
        for nm in frontier:
            for extra in aliases.get(nm, ()):
                if extra not in seen:
                    seen.add(extra)
                    nxt.add(extra)
        frontier = nxt
    return seen


def walk_functions(tree: ast.Module):
    """Yield (qualname, node) for every function/method, depth-first."""
    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{child.name}"
                yield q, child
                yield from visit(child, q + ".")
            elif isinstance(child, ast.ClassDef):
                yield from visit(child, f"{prefix}{child.name}.")
            else:
                yield from visit(child, prefix)
    yield from visit(tree, "")


#: annotations that declare a parameter host-static: concretizing one
#: (float()/bool()) is legal even under jit — shape/config math, not a
#: tracer. Anything else (unannotated, Array, pytree, ...) stays suspect.
_STATIC_ANNOTATIONS = frozenset({"int", "float", "bool", "str", "bytes"})


def tracer_suspect_params(fn: ast.FunctionDef | ast.AsyncFunctionDef
                          ) -> set[str]:
    """Parameter names that might carry tracers: every param EXCEPT
    those annotated with a static scalar type (``capacity: int`` is
    host shape math by declaration)."""
    a = fn.args
    out: set[str] = set()
    for p in a.posonlyargs + a.args + a.kwonlyargs:
        ann = p.annotation
        if ann is not None and _last(dotted(ann)) in _STATIC_ANNOTATIONS:
            continue
        out.add(p.arg)
    if a.vararg:
        out.add(a.vararg.arg)
    if a.kwarg:
        out.add(a.kwarg.arg)
    return out


class Rule:
    name = "RULE"
    doc = ""

    def run(self, files: list[SourceFile]) -> list[Finding]:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# JIT01 — host sync / impurity inside jit-reachable code
# ---------------------------------------------------------------------------

#: transforms whose function arguments are TRACED (bare or dotted use)
JIT_WRAPPERS = frozenset({
    "jit", "pjit", "pmap", "vmap", "grad", "value_and_grad",
    "checkpoint", "remat", "custom_vjp", "custom_jvp", "pallas_call",
    "shard_map",
})

#: higher-order tracing ops — dotted use only (``lax.scan``): a bare
#: ``map``/``cond`` is far more likely the builtin / a local helper
TRACE_HOFS = frozenset({
    "scan", "while_loop", "cond", "switch", "fori_loop",
    "associative_scan", "map", "defvjp", "defjvp",
})

#: host-escape callbacks: their arguments run on the host BY DESIGN —
#: nothing inside them is a JIT01 violation (the documented hatch)
CALLBACK_ESCAPES = frozenset({"io_callback", "pure_callback", "callback"})

#: methods every registered model exposes to the jit'd trainer/exporter
#: (the Model protocol's traced surface) — roots even with no local
#: jit marker, so models/*.py is covered without cross-module analysis
MODEL_PROTOCOL_ROOTS = frozenset({"loss", "eval_metrics"})

#: path fragments whose every function is jit-reachable by contract:
#: ops/** is the kernel/op library — anything in it may be called
#: under jit, so all of it must stay pure
JIT_MODULE_FRAGMENTS = ("/ops/",)


class Jit01(Rule):
    name = "JIT01"
    doc = ("host sync / wall clock / metrics mutation inside "
           "jit-reachable code")

    def run(self, files):
        out: list[Finding] = []
        for sf in files:
            out.extend(self._check_file(sf))
        return out

    # -- reachability ---------------------------------------------------
    def _roots_and_defs(self, sf: SourceFile):
        defs: dict[str, list] = {}
        quals: dict[int, str] = {}
        for qual, fn in walk_functions(sf.tree):
            defs.setdefault(fn.name, []).append(fn)
            quals[id(fn)] = qual

        aliases = collect_aliases(sf.tree)
        roots: set[int] = set()

        def mark(names: Iterable[str]):
            for nm in expand_aliases(set(names), aliases):
                for fn in defs.get(nm, ()):
                    roots.add(id(fn))

        # 1) decorator-marked defs
        for fns in defs.values():
            for fn in fns:
                if decorator_names(fn) & JIT_WRAPPERS:
                    roots.add(id(fn))
        # 2) call-site-marked defs: jit(f) / lax.scan(body, ...)
        for n in ast.walk(sf.tree):
            if not isinstance(n, ast.Call):
                continue
            name = dotted(n.func)
            lastseg = _last(name)
            if lastseg in JIT_WRAPPERS or (
                    lastseg in TRACE_HOFS and name and "." in name):
                for arg in n.args:
                    mark(identifiers(arg))
        # 3) protocol + module-policy roots
        in_ops = any(frag in "/" + sf.path
                     for frag in JIT_MODULE_FRAGMENTS)
        for nm, fns in defs.items():
            if nm in MODEL_PROTOCOL_ROOTS or in_ops:
                roots.update(id(fn) for fn in fns)

        # 4) propagate through same-module calls: f() / self.f()
        reachable = set(roots)
        frontier = list(roots)
        by_id = {id(fn): fn for fns in defs.values() for fn in fns}
        while frontier:
            fn = by_id[frontier.pop()]
            for n in ast.walk(fn):
                if not isinstance(n, ast.Call):
                    continue
                callee = None
                if isinstance(n.func, ast.Name):
                    callee = n.func.id
                elif isinstance(n.func, ast.Attribute) and isinstance(
                        n.func.value, ast.Name) and n.func.value.id in (
                        "self", "cls"):
                    callee = n.func.attr
                if callee is None:
                    continue
                for target in defs.get(callee, ()):
                    if id(target) not in reachable:
                        reachable.add(id(target))
                        frontier.append(id(target))
        return reachable, quals, by_id

    # -- violation scan -------------------------------------------------
    def _check_file(self, sf: SourceFile) -> list[Finding]:
        reachable, quals, by_id = self._roots_and_defs(sf)
        out: list[Finding] = []
        # top-level reachable functions only: a nested reachable def is
        # scanned as part of its parent (param scopes stack)
        nested: set[int] = set()
        for fid in reachable:
            for n in ast.walk(by_id[fid]):
                if n is not by_id[fid] and isinstance(
                        n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    nested.add(id(n))
        for fid in sorted(reachable - nested,
                          key=lambda i: by_id[i].lineno):
            fn = by_id[fid]
            self._scan(fn, sf, quals[fid], [tracer_suspect_params(fn)],
                       out)
        return out

    def _scan(self, node, sf, qual, param_stack, out):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan(child, sf, qual,
                           param_stack + [tracer_suspect_params(child)],
                           out)
                continue
            if isinstance(child, ast.Lambda):
                stack = param_stack + [{a.arg for a in (
                    child.args.args + child.args.kwonlyargs)}]
                body = child.body
                # the body EXPRESSION itself may be the offending call
                # (`lambda y: time.time()`): _scan only inspects
                # children, so check the root node here
                if isinstance(body, ast.Call):
                    if _last(dotted(body.func)) in CALLBACK_ESCAPES:
                        self._scan(body.func, sf, qual, stack, out)
                        continue
                    self._check_call(body, sf, qual, stack, out)
                self._scan(body, sf, qual, stack, out)
                continue
            if isinstance(child, ast.Call):
                if _last(dotted(child.func)) in CALLBACK_ESCAPES:
                    # the host-escape hatch: its args run host-side by
                    # design; only keep scanning the func expression
                    self._scan(child.func, sf, qual, param_stack, out)
                    continue
                self._check_call(child, sf, qual, param_stack, out)
            self._scan(child, sf, qual, param_stack, out)

    def _check_call(self, call: ast.Call, sf, qual, param_stack, out):
        def flag(msg):
            out.append(Finding(rule=self.name, path=sf.path,
                               line=call.lineno, symbol=qual,
                               message=msg))

        name = dotted(call.func)
        lastseg = _last(name)
        if isinstance(call.func, ast.Attribute):
            if lastseg == "item" and not call.args:
                flag("`.item()` forces a device->host sync inside "
                     "jit-reachable code")
                return
            if lastseg in ("inc", "observe"):
                flag(f"metrics mutation `.{lastseg}()` inside "
                     "jit-reachable code (registry counters are host "
                     "state; mutate them at the step boundary)")
                return
            if lastseg == "set":
                # x.at[i].set(v) is the functional array update — the
                # one `.set` that BELONGS in jit code
                recv = call.func.value
                at_update = (isinstance(recv, ast.Subscript)
                             and isinstance(recv.value, ast.Attribute)
                             and recv.value.attr == "at")
                if not at_update:
                    flag("`.set()` (gauge/metric mutation?) inside "
                         "jit-reachable code — only `.at[...].set()` "
                         "array updates belong here")
                return
        if name and name.startswith("time."):
            flag(f"`{name}()` reads the host wall clock inside "
                 "jit-reachable code (it evaluates ONCE at trace time)")
            return
        if name in ("jax.device_get", "device_get"):
            flag("`jax.device_get` inside jit-reachable code forces a "
                 "host sync")
            return
        if name and "." in name:
            base, attr = name.rsplit(".", 1)
            if base in ("np", "numpy") and attr in ("asarray", "array"):
                flag(f"`{name}()` materializes on host: on a tracer "
                     "this raises at runtime; use jnp instead")
                return
        if isinstance(call.func, ast.Name) and call.func.id in (
                "float", "bool") and len(call.args) == 1 \
                and isinstance(call.args[0], ast.Name):
            arg = call.args[0].id
            if any(arg in params for params in param_stack):
                flag(f"`{call.func.id}({arg})` on a traced argument "
                     "forces concretization (works only outside jit; "
                     "inside it raises TracerBoolConversionError)")


# ---------------------------------------------------------------------------
# DON01 — jitted train-step wrappers must declare donation
# ---------------------------------------------------------------------------

_DONATE_KWARGS = ("donate_argnums", "donate_argnames")


def _step_like(names: Iterable[str]) -> str | None:
    """The first identifier whose snake tokens include 'step' — the
    'this jit call wraps a train step' signal."""
    for nm in sorted(names):
        if "step" in _tokens(nm):
            return nm
    return None


class Don01(Rule):
    name = "DON01"
    doc = "jitted train-step wrappers must declare donation"

    def run(self, files):
        out: list[Finding] = []
        for sf in files:
            aliases = collect_aliases(sf.tree)

            for qual, fn in walk_functions(sf.tree):
                # decorator form: @jax.jit / @partial(jax.jit, ...)
                if "step" not in _tokens(fn.name):
                    continue
                for dec in fn.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    tname = _last(dotted(target))
                    jitlike = tname in ("jit", "pjit")
                    if isinstance(dec, ast.Call) and tname == "partial" \
                            and dec.args:
                        jitlike = _last(dotted(dec.args[0])) in ("jit",
                                                                 "pjit")
                    if not jitlike:
                        continue
                    kwargs = (
                        {kw.arg for kw in dec.keywords}
                        if isinstance(dec, ast.Call) else set())
                    if not kwargs & set(_DONATE_KWARGS):
                        out.append(Finding(
                            rule=self.name, path=sf.path, line=fn.lineno,
                            symbol=qual, message=self._msg(fn.name)))
                    break
            for n in ast.walk(sf.tree):
                if not isinstance(n, ast.Call) \
                        or _last(dotted(n.func)) not in ("jit", "pjit") \
                        or not n.args:
                    continue
                wrapped = _step_like(
                    expand_aliases(identifiers(n.args[0]), aliases))
                if wrapped is None:
                    continue
                if not {kw.arg for kw in n.keywords} & set(_DONATE_KWARGS):
                    out.append(Finding(
                        rule=self.name, path=sf.path, line=n.lineno,
                        symbol="", message=self._msg(wrapped)))
        return out

    @staticmethod
    def _msg(name: str) -> str:
        return (f"jit of step-like `{name}` declares no donate_argnums/"
                "donate_argnames — losing TrainState donation costs "
                "~+1.3 GiB peak on bert_long (tests/test_donation.py "
                "contract); declare donation (an empty tuple is an "
                "explicit, visible choice)")


# ---------------------------------------------------------------------------
# THR01 — scheduler-owned fields vs thread-marked methods
# ---------------------------------------------------------------------------

#: container/attribute mutators a @snapshot_view method must not call on
#: an owned field — a `self._live.clear()` keeps the attribute itself in
#: Load context, so ctx alone cannot see the write (and the runtime
#: sanitizer's read allowance equally lets the load through; this static
#: check is the only layer that catches mutation-through-method)
_VIEW_MUTATORS = frozenset({
    "clear", "pop", "popitem", "update", "setdefault", "append",
    "extend", "insert", "remove", "add", "discard", "sort", "reverse",
    "appendleft", "extendleft", "popleft", "__setitem__", "__delitem__",
})


class Thr01(Rule):
    name = "THR01"
    doc = ("@scheduler_owned fields only from @scheduler_thread methods "
           "or @snapshot_view reads")

    def run(self, files):
        out: list[Finding] = []
        for sf in files:
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.ClassDef):
                    owned = self._owned_fields(node)
                    if owned:
                        out.extend(self._check_class(sf, node, owned))
        return out

    @staticmethod
    def _owned_fields(cls: ast.ClassDef) -> frozenset[str]:
        for dec in cls.decorator_list:
            if isinstance(dec, ast.Call) and _last(
                    dotted(dec.func)) == "scheduler_owned":
                return frozenset(
                    a.value for a in dec.args
                    if isinstance(a, ast.Constant)
                    and isinstance(a.value, str))
        return frozenset()

    def _check_class(self, sf, cls, owned):
        out: list[Finding] = []
        for item in cls.body:
            if not isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if item.name == "__init__":
                continue          # construction precedes the thread
            decs = decorator_names(item)
            full = "scheduler_thread" in decs
            read_only = "snapshot_view" in decs
            if full:
                continue
            parents = {child: parent for parent in ast.walk(item)
                       for child in ast.iter_child_nodes(parent)}
            for n in ast.walk(item):
                if not (isinstance(n, ast.Attribute)
                        and isinstance(n.value, ast.Name)
                        and n.value.id == "self"
                        and n.attr in owned):
                    continue
                qual = f"{cls.name}.{item.name}"
                if read_only:
                    how = self._view_mutation(n, parents)
                    if how is None and isinstance(n.ctx, ast.Load):
                        continue
                    out.append(Finding(
                        rule=self.name, path=sf.path, line=n.lineno,
                        symbol=qual,
                        message=(f"@snapshot_view method writes "
                                 f"scheduler-owned field `{n.attr}`"
                                 + (f" ({how})" if how else "")
                                 + " — views read, only "
                                 "@scheduler_thread methods mutate")))
                else:
                    out.append(Finding(
                        rule=self.name, path=sf.path, line=n.lineno,
                        symbol=qual,
                        message=(f"scheduler-owned field `{n.attr}` "
                                 f"referenced from `{item.name}`, which "
                                 "is neither @scheduler_thread nor "
                                 "@snapshot_view — only the scheduler "
                                 "thread owns this state")))
        return out

    @staticmethod
    def _view_mutation(n: ast.Attribute, parents: dict) -> str | None:
        """Mutation of an owned field whose attribute node itself sits
        in Load context: ``self._live.clear()`` (mutator call),
        ``self._live[k] = v`` / ``del self._live[k]`` (item write), and
        ``self.cache.x = v`` (write-through) all load `self.<field>`
        first — ctx alone cannot see them. Returns a short description
        of the mutation, or None for a genuine read."""
        p = parents.get(n)
        if isinstance(p, ast.Subscript) and p.value is n \
                and not isinstance(p.ctx, ast.Load):
            return "item assignment through the view"
        if isinstance(p, ast.Attribute) and p.value is n:
            if not isinstance(p.ctx, ast.Load):
                return f"write through `.{p.attr}`"
            gp = parents.get(p)
            if isinstance(gp, ast.Call) and gp.func is p \
                    and p.attr in _VIEW_MUTATORS:
                return f"mutating call `.{p.attr}()`"
        return None


# ---------------------------------------------------------------------------
# OBS01 — metric-name literals must resolve to a registered metric
# ---------------------------------------------------------------------------

_METRIC_NAME_RE = re.compile(r"[a-z][a-z0-9]*(?:_[a-z0-9]+)+")
_REGISTER_METHODS = ("counter", "gauge", "histogram")


class Obs01(Rule):
    name = "OBS01"
    doc = "metric-name string literals must resolve to a registered metric"

    def run(self, files):
        registered: set[str] = set()
        register_calls: list[tuple[SourceFile, ast.Call]] = []
        for sf in files:
            for n in ast.walk(sf.tree):
                if isinstance(n, ast.Call) and isinstance(
                        n.func, ast.Attribute) \
                        and n.func.attr in _REGISTER_METHODS \
                        and n.args and isinstance(n.args[0], ast.Constant) \
                        and isinstance(n.args[0].value, str):
                    registered.add(n.args[0].value)
                    register_calls.append((sf, n))
        if not registered:
            return []
        # the naming convention is self-calibrating: the first and last
        # snake tokens of REGISTERED names define what "looks like a
        # metric name" (e.g. serving_* ... *_total) — so `train_x`
        # (a data key) never trips the rule, while a typo'd
        # `serving_decode_stepz_total` does
        prefixes = {_tokens(r)[0] for r in registered}
        suffixes = {_tokens(r)[-1] for r in registered}
        skip_spans: dict[str, list[tuple[int, int]]] = {}
        for sf, call in register_calls:
            skip_spans.setdefault(sf.path, []).append(
                (call.lineno, call.end_lineno or call.lineno))

        out: list[Finding] = []
        for sf in files:
            spans = skip_spans.get(sf.path, [])
            # docstrings / bare string statements are prose — collect
            # their Constant nodes first (skipping the ast.Expr in the
            # walk would NOT skip the Constant inside it)
            prose: set[int] = set()
            for n in ast.walk(sf.tree):
                if isinstance(n, ast.Expr) and isinstance(
                        n.value, ast.Constant):
                    prose.add(id(n.value))
            for n in ast.walk(sf.tree):
                if id(n) in prose:
                    continue
                if not (isinstance(n, ast.Constant)
                        and isinstance(n.value, str)):
                    continue
                s = n.value
                if s in registered or not _METRIC_NAME_RE.fullmatch(s):
                    continue
                toks = _tokens(s)
                if toks[0] not in prefixes or toks[-1] not in suffixes:
                    continue
                if any(a <= n.lineno <= b for a, b in spans):
                    continue
                out.append(Finding(
                    rule=self.name, path=sf.path, line=n.lineno,
                    symbol="",
                    message=(f"metric name {s!r} is never registered "
                             "with counter()/gauge()/histogram() — a "
                             "typo'd name the runtime dead-counter lint "
                             "cannot see (it only knows names that DO "
                             "get registered)")))
        return out


# ---------------------------------------------------------------------------
# TRC01 — span-name literals must resolve against docs/span_names.txt
# ---------------------------------------------------------------------------

#: the declared span-name inventory (drift-guarded by
#: tests/test_graftlint.py exactly like docs/known_failures.txt)
SPAN_NAMES_PATH = os.path.join(REPO_ROOT, "docs", "span_names.txt")

#: the span-recording entry points; BARE-name calls only — attribute
#: calls like a regex match's ``m.span(1)`` are a different function
_SPAN_FNS = frozenset({"span", "add_span"})


def load_span_inventory(path: str = SPAN_NAMES_PATH) -> set[str]:
    """docs/span_names.txt: one span name per line, '#' comments
    skipped — THE parser, shared with the tier-1 drift guard."""
    with open(path, encoding="utf-8") as f:
        return {ln.strip() for ln in f
                if ln.strip() and not ln.startswith("#")}


def collect_span_literals(files: Iterable[SourceFile]
                          ) -> dict[str, list[tuple[str, int]]]:
    """{span name -> [(path, line), ...]} over every statically-visible
    span name: the literal FIRST argument of a bare ``span()`` /
    ``add_span()`` call, a literal ``span_name=`` keyword argument, and
    a ``span_name`` parameter's literal default (the engine's
    decode/verify dispatch passes its span name through that
    parameter). Variable names are skipped — a heuristic documented on
    the rule; the drift guard keeps the inventory honest from the
    other side."""
    out: dict[str, list[tuple[str, int]]] = {}

    def add(name: str, sf: SourceFile, line: int) -> None:
        out.setdefault(name, []).append((sf.path, line))

    for sf in files:
        for n in ast.walk(sf.tree):
            if isinstance(n, ast.Call):
                if isinstance(n.func, ast.Name) \
                        and n.func.id in _SPAN_FNS and n.args \
                        and isinstance(n.args[0], ast.Constant) \
                        and isinstance(n.args[0].value, str):
                    add(n.args[0].value, sf, n.args[0].lineno)
                # the router's span wrapper: _rspan(ctx, rid, NAME,
                # t0, t1, ...) — a span-recording entry point too
                if _last(dotted(n.func)) == "_rspan" \
                        and len(n.args) >= 3 \
                        and isinstance(n.args[2], ast.Constant) \
                        and isinstance(n.args[2].value, str):
                    add(n.args[2].value, sf, n.args[2].lineno)
                for kw in n.keywords:
                    if kw.arg == "span_name" and isinstance(
                            kw.value, ast.Constant) and isinstance(
                            kw.value.value, str):
                        add(kw.value.value, sf, kw.value.lineno)
            elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                a = n.args
                params = a.posonlyargs + a.args + a.kwonlyargs
                defaults = ([None] * (len(a.posonlyargs + a.args)
                                      - len(a.defaults))
                            + list(a.defaults) + list(a.kw_defaults))
                for p, d in zip(params, defaults):
                    if p.arg == "span_name" and isinstance(
                            d, ast.Constant) and isinstance(
                            d.value, str):
                        add(d.value, sf, d.lineno)
    return out


class Trc01(Rule):
    name = "TRC01"
    doc = ("span-name literals must resolve against the "
           "docs/span_names.txt inventory")

    def run(self, files):
        try:
            inventory = load_span_inventory()
        except OSError as e:
            return [Finding(
                rule=self.name, path="docs/span_names.txt", line=0,
                symbol="",
                message=f"span-name inventory unreadable ({e}) — the "
                        "rule cannot resolve any span() name")]
        out: list[Finding] = []
        for name, sites in sorted(collect_span_literals(files).items()):
            if name in inventory:
                continue
            for path, line in sites:
                out.append(Finding(
                    rule=self.name, path=path, line=line, symbol="",
                    message=(f"span name {name!r} is not in "
                             "docs/span_names.txt — the stitcher and "
                             "trace summaries group lanes by span "
                             "name, so a typo'd name silently drops "
                             "the span from every grouped view; add "
                             "it to the inventory (and the drift "
                             "guard) or fix the typo")))
        return out


# ---------------------------------------------------------------------------
# CFG01 — config fields / CLI flags declared but never read
# ---------------------------------------------------------------------------

class Cfg01(Rule):
    name = "CFG01"
    doc = "config fields / CLI flags declared but never read"

    def run(self, files):
        declared: list[tuple[SourceFile, int, str, str]] = []
        reads: set[str] = set()
        for sf in files:
            is_config = sf.path.endswith("config.py")
            for n in ast.walk(sf.tree):
                if isinstance(n, ast.Attribute) and isinstance(
                        n.ctx, ast.Load):
                    reads.add(n.attr)
                elif isinstance(n, ast.Call):
                    fname = dotted(n.func)
                    if isinstance(n.func, ast.Name) \
                            and n.func.id == "getattr" \
                            and len(n.args) >= 2 and isinstance(
                                n.args[1], ast.Constant):
                        reads.add(str(n.args[1].value))
                    elif _last(fname) == "add_argument" and n.args \
                            and isinstance(n.args[0], ast.Constant) \
                            and isinstance(n.args[0].value, str) \
                            and n.args[0].value.startswith("--"):
                        dest = n.args[0].value.lstrip("-").replace(
                            "-", "_")
                        for kw in n.keywords:
                            if kw.arg == "dest" and isinstance(
                                    kw.value, ast.Constant):
                                dest = str(kw.value.value)
                        declared.append((sf, n.lineno, "flag",
                                         dest))
                elif is_config and isinstance(n, ast.ClassDef) \
                        and self._is_dataclass(n):
                    for st in n.body:
                        if isinstance(st, ast.AnnAssign) and isinstance(
                                st.target, ast.Name):
                            declared.append(
                                (sf, st.lineno, f"{n.name} field",
                                 st.target.id))
        out: list[Finding] = []
        for sf, line, kind, name in declared:
            if name in reads:
                continue
            what = ("config " + kind if kind.endswith("field")
                    else f"CLI flag --{name}")
            out.append(Finding(
                rule=self.name, path=sf.path, line=line, symbol="",
                message=(f"{what} ({name!r}) is declared but never "
                         "read anywhere in the package or experiments "
                         "— a silently ignored knob is worse than an "
                         "error: wire it up or delete it")))
        return out

    @staticmethod
    def _is_dataclass(cls: ast.ClassDef) -> bool:
        for dec in cls.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if _last(dotted(target)) == "dataclass":
                return True
        return False


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

ALL_RULES: tuple[Rule, ...] = (Jit01(), Don01(), Thr01(), Obs01(),
                               Trc01(), Cfg01())
RULES_BY_NAME = {r.name: r for r in ALL_RULES}


def get_rules(names: Sequence[str] | None = None) -> list[Rule]:
    if names is None:
        return list(ALL_RULES)
    unknown = sorted(set(names) - set(RULES_BY_NAME))
    if unknown:
        raise ValueError(f"unknown rule(s) {unknown}; have "
                         f"{sorted(RULES_BY_NAME)}")
    return [RULES_BY_NAME[n] for n in names]

"""Minimal REST predict server over an exported servable.

The reference era shipped trained models to TensorFlow Serving and
queried ``POST /v1/models/<name>:predict`` with ``{"instances": [...]}``
(the TF Serving REST API). This module provides that serving-runtime
role for this framework's artifacts — stdlib ``http.server`` around a
:class:`~.serving.ServableModel`, speaking the same request/response
shape:

    POST /v1/models/<name>:predict
    {"instances": [{"x": [...]}, ...]}          # row format, or
    {"inputs": {"x": [[...], ...]}}             # columnar format
    -> {"predictions": [[...], ...]}

    POST /v1/models/<name>:generate              # generator artifacts
    {"inputs": {"input_ids": [[...], ...]}, "seed": 7}
    -> {"generations": [[token ids], ...]}

    GET /v1/models/<name>                        # status probe
    -> {"model_version_status": [{"state": "AVAILABLE", ...}]}

``:generate`` serves :func:`~.serving.export_generator` artifacts (the
whole KV-cache decode is inside the StableHLO program); the ``rng`` of
a sampling artifact is synthesized server-side from the integer
``seed``, and ragged artifacts additionally take a ``prompt_mask``
feature. A generator artifact rejects ``:predict`` (and vice versa)
with a 400 naming the right route.

Batch-polymorphic artifacts (the export default) serve any instance
count; static-batch artifacts (the MoE fallback) serve any count UP TO
their exported batch — the server pads the request to the exported
batch (repeating the first instance; routing capacity is per-batch, so
padding only dilutes it) and truncates the response back to the actual
count. Above the exported batch is a 400.

Scheduling (round 9): with ``scheduler="on"`` (the default ``"auto"``
turns it on when the artifact carries stepwise generator programs),
requests no longer execute one-per-handler-thread:

- ``:generate`` routes through :class:`~.serving_batch.GenerationEngine`
  — concurrent requests share batched decode steps over one cache pool
  (continuous batching); prompts may be SHORTER than the exported
  prompt capacity (the engine right-packs them), and per-request
  ``max_new``/``temperature``/``top_k``/``top_p``/``seed`` ride the
  payload.
- ``:predict`` routes through :class:`~.serving_batch.MicroBatcher` —
  dynamic micro-batching up to ``batch_max_size`` rows or
  ``batch_max_wait_ms``.
- ``GET /stats`` (also ``/v1/models/<name>/stats``) reports queue
  depth, live slots, decode-dispatch counters (the steps-shared
  figure), and latency percentiles.
- a full admission queue is 429 + ``Retry-After`` — bounded admission
  replacing silent unbounded threading.

``scheduler="off"`` keeps the one-request-one-program path (now behind
a single-flight lock — ThreadingHTTPServer handler threads must not
race the executable) — the parity oracle the scheduler's byte-identical
greedy contract is tested against, and the right choice for offline
correctness work where cross-request batching would only add moving
parts.

Telemetry (round 11): the server owns ONE
:class:`~.obs.registry.Registry` shared with its engine/batcher, so

- ``GET /metrics`` serves Prometheus text format rendered from the
  same atomic snapshot ``/stats`` reads — the two views cannot drift;
- ``POST /trace/start`` arms the in-process span recorder
  (``--trace_buffer_events`` bounds the ring) and ``POST /trace/stop``
  returns the capture as chrome://tracing / Perfetto trace-event JSON
  (per-slot scheduler lanes, request-ID-correlated);
- scheduled ``:generate`` responses carry ``request_ids`` and a
  per-request ``timings`` breakdown (queue_ms / prefill_ms /
  decode_ms / tokens); a client ``X-Request-Id`` header propagates
  (row i of a multi-row request gets ``<id>-<i>``), and
  ``--request_log PATH`` streams one structured JSONL event per
  retired request through :class:`~.utils.metrics.MetricsLogger`;
- ``--metrics off`` disables the registry (every increment becomes a
  single branch) for overhead-sensitive parity work.

Self-healing (round 14): the server fronts the engine's failure
contract —

- ``deadline_ms`` in the ``:generate`` payload (or
  ``--default_deadline_ms``) bounds each request; expiry answers 504
  naming the budget, and the slot + cache blocks are already back in
  the pool when the response leaves;
- ``POST /cancel/<request_id>`` cancels a queued or live request
  (200/404); the cancelled request's own waiter gets 409;
- ``GET /healthz`` reports the scheduler watchdog (``live`` /
  ``stalled`` / ``dead`` with the heartbeat age) — 200 only when live,
  so a wedged scheduler thread fails load-balancer probes instead of
  silently blackholing traffic;
- SIGTERM (and ``stop()``) triggers a graceful drain: new admissions
  answer 503 + Retry-After while queued/in-flight requests finish
  under ``--drain_timeout_s``, the request log flushes, and a
  scheduler that never parks raises ``EngineStalledError`` naming the
  last-heartbeat age;
- the ``http.read`` fault seam (``--fault_spec``-driven, inert by
  default) covers the request-read path for the serving chaos soak
  (``experiments/serving_chaos.py``).

Speculative decoding (round 16): ``--spec_tokens K`` arms the engine's
self-drafting draft-and-verify loop over artifacts exported with a
verify program (``export_generator(..., spec_tokens=K)``); an artifact
WITHOUT one auto-falls back to spec-off with a logged warning instead
of refusing to serve (the knob is an optimization, not a contract).
Per-request payload knobs: ``spec_tokens`` (0 opts a request out, or a
lower cap), and ``stop_sequences`` (a list of token-id sequences —
generation retires the moment the output ends with any of them, the
match truncated from the response; works with speculation on or off at
identical boundaries). ``/stats`` and ``/metrics`` carry
``accept_rate`` and the ``serving_spec_*`` counters; each response's
``timings`` rows carry ``spec_accepted``.

SLO-aware overload resilience (round 18): ``--prefill_chunk_tokens C``
arms chunked prefill over artifacts exported with
``export_generator(..., prefill_chunk=C)`` (auto-off with a warning
otherwise — byte-identical greedy output either way);
``--default_priority`` and the per-request ``priority`` payload knob
(``interactive`` | ``batch`` | ``best_effort``) order the admission
queue (class, earliest deadline, FIFO, aging); ``--shed_policy auto``
runs the brownout ladder plus the deadline-feasibility shed — shed
requests answer 429 with a MEASURED ``Retry-After``
(:class:`~.serving_batch.ShedError` is a ``QueueFullError``, so the
existing 429 mapping carries it), never a timeout. ``GET /healthz``
now publishes the saturation fields (``queue_age_s`` /
``queue_limit`` / ``pressure`` / ``saturated``) the fleet router uses
to demote an overloaded-but-live replica to ``degraded`` before it
starts mass-shedding; ``/stats`` and ``/metrics`` carry the
``serving_shed_*`` / pressure / chunk counters and the
``serving_decode_stall_seconds`` histogram.

SLO attainment & goodput observability (round 19, DESIGN.md §22):
``--history_interval_s S`` arms a :class:`~.obs.timeseries.
SnapshotSampler` — the atomic registry snapshot captured into a
bounded ring every S seconds, served as ``GET /stats/history`` (a
poll also captures a fresh sample, so the endpoint is always
current) — and evaluates ``--slo_spec`` objectives
(:mod:`~.obs.slo`) over it on every capture: per-class attainment +
fast/slow burn rates ride ``/stats/history``, an ADVISORY ``slo``
block rides ``/healthz`` (never the status code), and a multi-window
burn breach writes a rate-limited ``slo_burn`` incident bundle
(objectives, burn rates, history tail, registry snapshot) through
the flight recorder. Off (the default) is a provable no-op: no
sampler exists and no request-path code looks for one —
``tools/servetop.py`` renders the endpoint live or from a dump.

Fleet (round 15): N of these servers sit behind
:class:`~.serving_router.ReplicaRouter` — ``/healthz`` (live/stalled/
draining) drives the router's replica state machine, ``POST
/cancel/<rid>`` is the hedging loser-cancellation path, and
:meth:`PredictServer.kill` is the chaos harness's crash switch
(listener down NOW, no drain — the ``replica.crash`` seam).

Distributed tracing + flight recorder (round 17, DESIGN.md §20): an
inbound ``traceparent`` header (the router's per-attempt context)
parents the engine's slot-lane spans under the fleet trace instead of
a fresh local root, and ``:generate`` responses return ``trace_id``
beside ``request_ids``; ``GET /trace/export`` drains this server's
spans (its own process label — in-process fleet replicas share one
ring) for the router's ``GET /trace/fleet`` stitcher, and ``/healthz``
carries ``mono_now`` for the stitcher's clock-offset estimate. With
``--flight_recorder on`` (default) the span ring runs ALWAYS-ON and
the failure seams (watchdog stall here; engine-fatal rebuild and
poison eviction in the engine) auto-write rate-limited incident
bundles to ``--incident_dir`` — registry snapshot, span tail,
request-log tail, config fingerprint — with ``off`` byte- and
dispatch-identical (armed-vs-plain parity, tier-1).
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any

import numpy as np

from .obs import prom as obs_prom
from .obs import slo as obs_slo
from .obs import timeseries as obs_ts
from .obs import trace as obs_trace
from .obs.flightrec import FlightRecorder
from .obs.registry import Registry
from .runtime import faults
from .runtime.device import enable_compilation_cache
from .serving import ServableModel, has_stepwise, load_servable
from .serving_batch import (DeadlineExceededError, DrainingError,
                            EngineStalledError, GenerationEngine,
                            MicroBatcher, QueueFullError,
                            RequestCancelledError)


class _ServerFault(Exception):
    """Wraps an exception raised by the EXECUTABLE (platform mismatch,
    runtime OOM, ...) so the HTTP layer can answer 500 even when the
    underlying type is ValueError/TypeError — the client-fault types the
    request-validation path maps to 400. jax.export's call raises
    ValueError for a served-on-wrong-platform artifact; without the
    wrapper that server-side failure would be blamed on the client."""


class PredictServer:
    """Serve one exported model directory over HTTP.

    >>> srv = PredictServer(export_dir)        # name defaults to meta
    >>> srv.start()                            # background thread
    >>> ... POST http://localhost:{srv.port}/v1/models/<name>:predict
    >>> srv.stop()
    """

    def __init__(self, export_dir: str, *, name: str | None = None,
                 host: str = "127.0.0.1", port: int = 0,
                 scheduler: str = "auto", batch_max_size: int = 8,
                 batch_max_wait_ms: float = 5.0, max_queue: int = 64,
                 prefix_cache: bool = True, metrics: bool = True,
                 trace_buffer_events: int = 65536,
                 request_log: str | None = None,
                 thread_sanitizer: bool = False,
                 default_deadline_ms: int = 0,
                 drain_timeout_s: float = 30.0,
                 stall_after_s: float = 10.0,
                 spec_tokens: int = 0,
                 prefill_chunk_tokens: int = 0,
                 default_priority: str = "interactive",
                 shed_policy: str = "auto",
                 priority_aging_ms: int = 2000,
                 process_name: str | None = None,
                 flight_recorder: bool = True,
                 incident_dir: str | None = None,
                 history_interval_s: float = 0.0,
                 history_samples: int = 600,
                 slo_spec: str | None = None,
                 slo_fast_window_s: float = obs_slo.FAST_WINDOW_S,
                 slo_slow_window_s: float = obs_slo.SLOW_WINDOW_S,
                 slo_burn_threshold: float = obs_slo.BURN_THRESHOLD,
                 history_clock=None):
        if scheduler not in ("auto", "on", "off"):
            raise ValueError(f"scheduler must be auto/on/off, got "
                             f"{scheduler!r}")
        self.servable: ServableModel = load_servable(export_dir)
        self.name = name or self.servable.meta.get("model", "model")
        # trace-lane process label: "serving" standalone; an in-process
        # fleet names each replica so the shared ring's per-process
        # drain (GET /trace/export) segregates their spans
        self.process_name = process_name or "serving"
        # one registry for the whole server (engine/batcher counters +
        # the HTTP-level ones below); metrics=False disables every
        # increment behind a single branch
        self.registry = Registry(enabled=metrics, namespace="serving")
        self._c_http_requests = self.registry.counter(
            "http_requests_total", "HTTP requests handled")
        self._c_http_errors = self.registry.counter(
            "http_errors_total", "HTTP responses with status >= 400")
        # quant observability: a generator artifact from before the
        # quant metadata schema can still be served (it simply has no
        # quantized paths), but the operator should see that it
        # predates quant support rather than assume --weight_quant /
        # --kv_cache_dtype took effect
        self._c_quant_fallback = self.registry.counter(
            "serving_quant_fallback_total",
            "generator artifacts loaded without quant metadata "
            "(exported before the quant schema — no quantized paths)")
        if (self.servable.meta.get("kind") == "generator"
                and self.servable.meta.get("quant_schema") is None):
            self._c_quant_fallback.inc()
        self._request_logger = None
        if request_log:
            from .utils.metrics import MetricsLogger
            self._request_logger = MetricsLogger(request_log)
        # flight recorder (round 17): the bounded ring runs ALWAYS-ON
        # (arm_always_on never clears a capture someone else armed), so
        # an incident bundle has history without anyone having POSTed
        # /trace/start first; --flight_recorder off reverts to the
        # armed-on-demand ring (byte- and dispatch-identical serving —
        # the armed-vs-plain parity contract)
        if flight_recorder:
            obs_trace.arm_always_on(trace_buffer_events)
        else:
            obs_trace.ensure_capacity(trace_buffer_events)
        self._c_incidents = self.registry.counter(
            "serving_incidents_total",
            "incident bundles written by the flight recorder")
        self._c_incidents_suppressed = self.registry.counter(
            "serving_incidents_suppressed_total",
            "incident bundles suppressed by the per-cause rate limit")
        self._flightrec = None
        if flight_recorder and incident_dir:
            self._flightrec = FlightRecorder(
                incident_dir, process=self.process_name,
                snapshot_fn=self._metrics_snapshot,
                config={"scheduler": scheduler,
                        "max_queue": max_queue,
                        "prefix_cache": prefix_cache,
                        "metrics": metrics,
                        "trace_buffer_events": trace_buffer_events,
                        "default_deadline_ms": default_deadline_ms,
                        "drain_timeout_s": drain_timeout_s,
                        "stall_after_s": stall_after_s,
                        "spec_tokens": spec_tokens,
                        "prefill_chunk_tokens": prefill_chunk_tokens,
                        "default_priority": default_priority,
                        "shed_policy": shed_policy,
                        "export_dir": export_dir,
                        "model": self.name},
                request_log_path=request_log,
                counter=self._c_incidents,
                suppressed_counter=self._c_incidents_suppressed)
        # ---- SLO observability (round 19): metric time-series +
        # burn-rate evaluation. OFF by default (--history_interval_s
        # 0): no sampler object exists and NO request-path code ever
        # consults one — the sampler is a pure registry READER on its
        # own thread, so arming it is byte- and dispatch-identical
        # serving (the armed-vs-plain contract the smoke slo_on leg
        # pins). GET /stats/history also captures a fresh sample, so
        # a poll always sees the current instant and tests drive the
        # ring without sleeping.
        if history_interval_s < 0:
            raise ValueError(f"history_interval_s must be >= 0 (0 = "
                             f"sampler off), got {history_interval_s}")
        if slo_spec and not history_interval_s:
            raise ValueError(
                "--slo_spec declares objectives but --history_interval_s "
                "is 0 — burn rates are windowed over the history ring; "
                "arm the sampler to evaluate them")
        self.slo_fast_window_s = float(slo_fast_window_s)
        self.slo_slow_window_s = float(slo_slow_window_s)
        self.slo_burn_threshold = float(slo_burn_threshold)
        self._slo_objectives: list[obs_slo.Objective] = []
        self._slo_lock = threading.Lock()
        self._slo_results: list[dict] | None = None
        self._sampler = None
        if history_interval_s:
            self._slo_objectives = (obs_slo.parse_slo_spec(slo_spec)
                                    if slo_spec
                                    else obs_slo.default_objectives())
            # a p95_ms target beyond the latency histograms' finite
            # bucket coverage is unmeasurable: requests landing in the
            # +Inf bucket cannot be classified against it, and the
            # pessimistic count would page spurious breaches forever —
            # refuse the misconfiguration loudly at arm time
            from .obs.registry import SERVING_LATENCY_BUCKETS
            top_ms = max(SERVING_LATENCY_BUCKETS) * 1e3
            for o in self._slo_objectives:
                if o.kind == "p95_ms" and o.target > top_ms:
                    raise ValueError(
                        f"slo_spec objective {o.key()}: target "
                        f"{o.target:g} ms exceeds the latency "
                        f"histograms' largest finite bucket "
                        f"({top_ms:g} ms) — observations beyond it "
                        "are indistinguishable, so this objective "
                        "cannot be evaluated; lower the target or "
                        "widen SERVING_LATENCY_BUCKETS")
            kw = {"clock": history_clock} if history_clock else {}
            self._sampler = obs_ts.SnapshotSampler(
                self._metrics_snapshot,
                interval_s=history_interval_s,
                max_samples=history_samples,
                on_sample=self._on_history_sample, **kw)
        # the single-flight lock for the direct path: _execute is called
        # from ThreadingHTTPServer handler threads, and nothing else
        # serializes the executable (the scheduler paths serialize by
        # construction — one scheduler thread owns all executable calls)
        self._exec_lock = threading.Lock()
        is_gen = self.servable.meta.get("kind") == "generator"
        stepwise = has_stepwise(export_dir)
        if scheduler == "auto":
            # ON exactly when the artifact can be scheduled: stepwise
            # generator programs for :generate, or a predict artifact
            # (micro-batching needs nothing extra) stays off by default
            # to keep the plain server a pure parity tool
            scheduler = "on" if (is_gen and stepwise) else "off"
        self.scheduler = scheduler
        if thread_sanitizer and not (scheduler == "on" and is_gen):
            # checked BEFORE anything starts: a raise must not leave a
            # running batcher behind
            raise ValueError(
                "thread_sanitizer=True guards the GenerationEngine's "
                "scheduler-owned fields, but this server would run the "
                f"{'predict/MicroBatcher' if not is_gen else 'plain'} "
                f"path (scheduler {scheduler!r}, kind "
                f"{self.servable.meta.get('kind')!r}) where nothing is "
                "guarded — drop the flag or serve stepwise generator "
                "artifacts with scheduler on/auto")
        self.engine: GenerationEngine | None = None
        self.batcher: MicroBatcher | None = None
        if scheduler == "on":
            if is_gen:
                if not stepwise:
                    raise ValueError(
                        f"scheduler='on' needs stepwise generator "
                        f"artifacts in {export_dir!r} — re-export with "
                        "export_generator(..., stepwise=True), or serve "
                        "with scheduler='off'")
                from .serving import load_stepwise
                sw = load_stepwise(export_dir)
                if spec_tokens and not sw.spec_tokens:
                    # auto-off: the knob asks for an optimization this
                    # artifact cannot run — serve without it (loudly)
                    # rather than refuse traffic
                    from .utils.logging import get_logger
                    get_logger("serving").warning(
                        "--spec_tokens %d requested but %r carries no "
                        "verify program (exported without spec_tokens) "
                        "— speculative decoding disabled for this "
                        "server; re-export with export_generator(..., "
                        "spec_tokens=K) to enable it", spec_tokens,
                        export_dir)
                    spec_tokens = 0
                elif spec_tokens > sw.spec_tokens:
                    from .utils.logging import get_logger
                    get_logger("serving").warning(
                        "--spec_tokens %d exceeds this artifact's "
                        "exported verify width %d — clamping to %d",
                        spec_tokens, sw.spec_tokens, sw.spec_tokens)
                    spec_tokens = sw.spec_tokens
                if prefill_chunk_tokens \
                        and not sw.prefill_chunk_tokens:
                    # auto-off, same contract as --spec_tokens: the
                    # knob asks for an optimization this artifact
                    # cannot run — serve without it (loudly) rather
                    # than refuse traffic
                    from .utils.logging import get_logger
                    get_logger("serving").warning(
                        "--prefill_chunk_tokens %d requested but %r "
                        "carries no chunked-prefill program (exported "
                        "without prefill_chunk) — chunked prefill "
                        "disabled for this server; re-export with "
                        "export_generator(..., prefill_chunk=C) to "
                        "enable it", prefill_chunk_tokens, export_dir)
                    prefill_chunk_tokens = 0
                elif prefill_chunk_tokens > sw.prefill_chunk_tokens \
                        and sw.prefill_chunk_tokens:
                    from .utils.logging import get_logger
                    get_logger("serving").warning(
                        "--prefill_chunk_tokens %d exceeds this "
                        "artifact's exported chunk width %d — "
                        "clamping to %d", prefill_chunk_tokens,
                        sw.prefill_chunk_tokens,
                        sw.prefill_chunk_tokens)
                    prefill_chunk_tokens = sw.prefill_chunk_tokens
                self.engine = GenerationEngine(
                    sw, max_queue=max_queue,
                    prefix_cache=prefix_cache, registry=self.registry,
                    metrics_logger=self._request_logger,
                    thread_sanitizer=thread_sanitizer,
                    default_deadline_ms=default_deadline_ms,
                    drain_timeout_s=drain_timeout_s,
                    stall_after_s=stall_after_s,
                    spec_tokens=spec_tokens,
                    prefill_chunk_tokens=prefill_chunk_tokens,
                    default_priority=default_priority,
                    shed_policy=shed_policy,
                    priority_aging_ms=priority_aging_ms,
                    process=self.process_name,
                    flight_recorder=self._flightrec).start()
                if self._flightrec is not None:
                    # the recorder's config block was snapshotted with
                    # the REQUESTED knobs; the auto-off/clamp logic
                    # above may have changed what actually runs — an
                    # incident bundle must name the effective values
                    self._flightrec.config.update({
                        "spec_tokens": self.engine.spec_tokens,
                        "prefill_chunk_tokens":
                            self.engine.prefill_chunk_tokens})
            else:
                self.batcher = MicroBatcher(
                    self.servable, batch_max_size=batch_max_size,
                    batch_max_wait_ms=batch_max_wait_ms,
                    max_queue=max_queue,
                    registry=self.registry,
                    process=self.process_name).start()
        # socketserver listens with a backlog of 5: a wave of more
        # clients than that connecting at once has its SYNs dropped and
        # retried 1-63 s later, unseen by the server, and a request whose
        # body was in flight meanwhile can die on the handler's read
        # timeout. The listener holds what the admission queue may hold.
        httpd_cls = type("PredictHTTPServer", (ThreadingHTTPServer,),
                         {"request_queue_size": max(5, int(max_queue))})
        self._httpd = httpd_cls((host, port), self._make_handler())
        self.port = self._httpd.server_address[1]
        self._thread: threading.Thread | None = None

    # -- request plumbing ----------------------------------------------
    def _feature_arrays(self, payload: dict, sig: dict | None = None,
                        *, pad_static: bool = True
                        ) -> dict[str, np.ndarray]:
        if sig is None:
            sig = self.servable.input_signature
        if "instances" in payload:
            rows = payload["instances"]
            if not isinstance(rows, list) or not rows:
                raise ValueError("'instances' must be a non-empty list")
            if not isinstance(rows[0], dict):
                if len(sig) != 1:
                    raise ValueError(
                        f"bare instances need a single-input model; "
                        f"this one takes {sorted(sig)}")
                only = next(iter(sig))
                rows = [{only: r} for r in rows]
            keys = set(rows[0])
            for i, r in enumerate(rows):
                if not isinstance(r, dict) or set(r) != keys:
                    # a key present only in LATER rows would silently
                    # vanish from the column build below — the exact
                    # dropped-feature failure the unknown-input check
                    # exists to reject
                    raise ValueError(
                        f"instance {i} keys {sorted(r) if isinstance(r, dict) else type(r).__name__} "
                        f"differ from instance 0 keys {sorted(keys)}")
            cols = {k: [r[k] for r in rows] for k in keys}
        elif "inputs" in payload:
            cols = payload["inputs"]
            if not isinstance(cols, dict):
                if len(sig) != 1:
                    raise ValueError(
                        f"bare inputs need a single-input model; this "
                        f"one takes {sorted(sig)}")
                cols = {next(iter(sig)): cols}
        else:
            raise ValueError("request needs 'instances' or 'inputs'")
        missing = set(sig) - set(cols)
        if missing:
            raise ValueError(f"missing model inputs {sorted(missing)} "
                             f"(want {sorted(sig)})")
        unknown = set(cols) - set(sig)
        if unknown:
            # a silently dropped feature is worse than an error: e.g. a
            # prompt_mask POSTed to a generator exported WITHOUT
            # ragged=True would otherwise be discarded and the pad ids
            # decoded as real prompt tokens, 200 OK
            raise ValueError(f"unknown model inputs {sorted(unknown)} "
                             f"(this artifact takes {sorted(sig)})")
        out = {}
        counts = set()
        for key, spec in sig.items():
            arr = np.asarray(cols[key], dtype=np.dtype(spec["dtype"]))
            want_tail = tuple(spec["shape"][1:])
            if arr.shape[1:] != want_tail:
                raise ValueError(
                    f"input {key!r} has per-instance shape "
                    f"{arr.shape[1:]}, model wants {want_tail}")
            counts.add(arr.shape[0])
            out[key] = arr
        if len(counts) != 1:
            raise ValueError(
                f"inputs disagree on instance count: {sorted(counts)}")
        n = counts.pop()
        if n == 0:
            # np.repeat(v[:1], ...) on an empty array still yields 0
            # rows, so the static-batch pad below would hand the
            # executable an empty batch and the client would see an
            # opaque 500 — reject the empty request as the 400 it is
            raise ValueError("request contains zero instances")
        if not self.servable.meta.get("batch_polymorphic", True):
            # static-batch artifact (e.g. MoE fallback): pad up to the
            # exported batch and let predict() truncate — only MORE
            # instances than the executable can take is the client's
            # error. Padding repeats the first instance; MoE routing
            # capacity is per-batch, so pad rows only dilute it (they
            # can steal expert slots from real rows only when the real
            # request would itself be near overflow).
            # NOTE: Switch-MoE predictions are inherently batch-
            # composition-dependent (routing capacity is per batch), so
            # a padded request is exactly as valid as any other batch
            # the real rows could have shared — but at tight capacity
            # identical pad rows CAN crowd an expert and degrade the
            # real rows; export with headroom (capacity_factor) if
            # serving small requests against a static batch
            b_exp = next(iter(sig.values()))["shape"][0]
            if n > b_exp:
                raise ValueError(
                    f"this artifact was exported with a static batch of "
                    f"{b_exp} instances; got {n} (requests up to {b_exp} "
                    "are padded server-side)")
            if n < b_exp and pad_static:
                # pad_static=False: the micro-batcher pads AFTER merging
                # requests — padding here would waste its shared rows
                out = {k: np.concatenate(
                    [v, np.repeat(v[:1], b_exp - n, axis=0)])
                    for k, v in out.items()}
        return out, n

    def _execute(self, feats) -> np.ndarray:
        # single-flight: handler threads serialize on the executable —
        # concurrent dispatch of one jitted callable from N threads is
        # not a contract jax gives us, and "accidentally working" is
        # not thread safety
        try:
            with self._exec_lock:
                return np.asarray(self.servable(feats))
        except Exception as e:
            raise _ServerFault(f"{type(e).__name__}: {e}") from e

    def predict(self, payload: dict,
                request_id: str | None = None,
                trace: obs_trace.TraceContext | None = None) -> dict:
        if self.servable.meta.get("kind") == "generator":
            raise ValueError(
                "this artifact is a generator — POST to :generate")
        if self.batcher is not None:
            feats, n = self._feature_arrays(payload, pad_static=False)
            preds = self.batcher.submit(feats, n).result(timeout=300)
            return {"predictions": np.asarray(preds).tolist()}
        feats, n = self._feature_arrays(payload)
        logits = self._execute(feats)
        # truncate any server-side padding back to the client's count
        return {"predictions": logits[:n].tolist()}

    def _prompt_limit(self) -> int | None:
        """The exported prompt capacity (explicit metadata since round
        9; the input signature's second dim for older artifacts)."""
        pl = self.servable.meta.get("prompt_len")
        if pl is not None:
            return int(pl)
        spec = self.servable.input_signature.get("input_ids")
        return int(spec["shape"][1]) if spec else None

    def _check_prompt_lengths(self, payload: dict) -> None:
        """A prompt longer than the artifact's capacity must be a 400
        NAMING the limit — without this check it surfaces either as an
        opaque shape-mismatch message or (ragged JSON rows) as numpy's
        'setting an array element with a sequence'."""
        limit = self._prompt_limit()
        if limit is None:
            return
        rows = None
        if isinstance(payload.get("inputs"), dict):
            rows = payload["inputs"].get("input_ids")
        elif isinstance(payload.get("instances"), list):
            rows = [r.get("input_ids") for r in payload["instances"]
                    if isinstance(r, dict)]
        if not isinstance(rows, list):
            return                     # malformed: canonical checks handle
        for i, row in enumerate(rows):
            if isinstance(row, (list, np.ndarray)) and len(row) > limit:
                raise ValueError(
                    f"prompt {i} has {len(row)} tokens, which exceeds "
                    f"this artifact's exported prompt capacity {limit} "
                    "(prompt_len in export.json; re-export with a "
                    "larger prompt_len to serve longer prompts)")

    def _generate_scheduled(self, payload: dict,
                            request_id: str | None = None,
                            trace: obs_trace.TraceContext | None = None
                            ) -> dict:
        """:generate via the continuous-batching engine: each instance
        row becomes one scheduler request (row i of a multi-row request
        samples under ``seed + i`` so rows stay independent). Rows may
        be SHORTER than the exported prompt capacity — the engine
        right-packs ragged prompts natively — and an all-pad
        ``prompt_mask`` row is rejected like the direct path.

        Every row gets a request id (the client's ``X-Request-Id``, or
        an engine-generated one) that travels to retirement; the
        response carries ``request_ids`` plus the per-request
        ``timings`` breakdown next to ``generations``."""
        self._check_prompt_lengths(payload)
        rows = None
        if isinstance(payload.get("inputs"), dict):
            rows = payload["inputs"].get("input_ids")
            masks = payload["inputs"].get("prompt_mask")
        elif isinstance(payload.get("instances"), list):
            inst = payload["instances"]
            if not all(isinstance(r, dict) for r in inst):
                raise ValueError("generate instances must be dicts with "
                                 "'input_ids'")
            bad_keys = set().union(*[set(r) for r in inst]) \
                - {"input_ids", "prompt_mask"}
            if bad_keys:
                raise ValueError(
                    f"unknown model inputs {sorted(bad_keys)} (the "
                    "scheduler takes input_ids and prompt_mask)")
            rows = [r.get("input_ids") for r in inst]
            masks = ([r.get("prompt_mask") for r in inst]
                     if any("prompt_mask" in r for r in inst) else None)
        else:
            raise ValueError("request needs 'instances' or 'inputs'")
        if not isinstance(rows, list) or not rows or any(
                r is None for r in rows):
            raise ValueError("generate needs non-empty 'input_ids' rows")
        if masks is not None and len(masks) != len(rows):
            raise ValueError("prompt_mask row count != input_ids rows")
        unknown = (set(payload.get("inputs", {}))
                   - {"input_ids", "prompt_mask"}
                   if isinstance(payload.get("inputs"), dict) else set())
        if unknown:
            raise ValueError(f"unknown model inputs {sorted(unknown)} "
                             "(the scheduler takes input_ids and "
                             "prompt_mask)")

        def knob(name, conv):
            v = payload.get(name)
            if v is None:
                return None
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError(f"{name!r} must be a number, got {v!r}")
            return conv(v)

        kw = {"max_new": knob("max_new", int),
              "temperature": knob("temperature", float),
              "top_k": knob("top_k", int),
              "top_p": knob("top_p", float),
              # per-request latency budget (ms; engine default applies
              # when absent) — expiry retires the slot between steps
              # and answers 504
              "deadline_ms": knob("deadline_ms", int),
              # per-request speculative width: 0 opts this request out
              # of drafting, 2..--spec_tokens caps it (absent = the
              # server default; >0 on a spec-off server is a 400)
              "spec_tokens": knob("spec_tokens", int)}
        prio = payload.get("priority")
        if prio is not None:
            # string knob (interactive|batch|best_effort): the value
            # set is validated in the engine's _make_request on this
            # handler thread — a bad class is a clean 400 naming the
            # choices; the type check here keeps the error readable
            if not isinstance(prio, str):
                raise ValueError(
                    f"'priority' must be a string, got {prio!r}")
            kw["priority"] = prio
        stop = payload.get("stop_sequences")
        if stop is not None:
            # shape/type validation happens in the engine's
            # _make_request (on this handler thread), so a bad list is
            # a clean 400 naming the offending row
            kw["stop_sequences"] = stop
        seed = payload.get("seed", 0)
        if isinstance(seed, bool) or not isinstance(seed, int):
            raise ValueError(f"'seed' must be an integer, got {seed!r}")
        prompts = []
        for i, row in enumerate(rows):
            prompt = np.asarray(row, np.int32).reshape(-1)
            if masks is not None and masks[i] is not None:
                mask = np.asarray(masks[i]).reshape(-1)
                if mask.shape != prompt.shape:
                    raise ValueError(
                        f"prompt_mask row {i} shape {mask.shape} != "
                        f"input_ids row shape {prompt.shape}")
                if not np.any(mask != 0):
                    raise ValueError("every prompt_mask row needs at "
                                     "least one real token")
                prompt = prompt[mask != 0]
            prompts.append(prompt)
        rids = None
        if request_id:
            rids = ([request_id] if len(prompts) == 1 else
                    [f"{request_id}-{i}" for i in range(len(prompts))])
        # submit_many validates EVERY row before queueing ANY, and the
        # enqueue is atomic — a 400/429 on row k must not leave rows
        # 0..k-1 generating for a client that already got an error
        # submit_many returns EngineHandles: result() cancels on
        # wall-timeout — a handler thread giving up must return the
        # slot + cache blocks to the pool, not abandon a request
        # decoding to max_new (the round-9 leak)
        # a propagated traceparent (the router's forward attempt)
        # parents the engine's slot-lane spans instead of a fresh
        # local root; an unsampled context contributes nothing
        trace_args = trace.span_args() if trace is not None else {}
        handles = self.engine.submit_many(prompts, seed=seed,
                                          request_ids=rids,
                                          trace=trace_args or None,
                                          **kw)

        def wait_all() -> list:
            try:
                return [h.result(timeout=300) for h in handles]
            except BaseException:
                # one row's failure is the WHOLE response's failure
                # (the client gets a single error): sibling rows must
                # not keep decoding for nobody — cancel every handle
                # still running before surfacing the error
                for h in handles:
                    if not h.done():
                        h.cancel()
                raise

        try:
            gens = wait_all()
        except (DeadlineExceededError, RequestCancelledError):
            raise          # the handler maps these to 504 / 409
        except (TimeoutError, RuntimeError) as e:
            raise _ServerFault(f"{type(e).__name__}: {e}") from e
        out = {"generations": gens,
               "request_ids": [h.request_id for h in handles],
               "timings": [h.timings for h in handles]}
        if trace is not None:
            # the trace id rides the response beside request_ids so a
            # client (or the router's annotation) can fetch the
            # stitched timeline for exactly this request
            out["trace_id"] = trace.trace_id
        return out

    def generate(self, payload: dict,
                 request_id: str | None = None,
                 trace: obs_trace.TraceContext | None = None) -> dict:
        """The decode route: ``{"inputs": {"input_ids": [[...]], ...},
        "seed": 7}`` -> ``{"generations": [[token ids]]}``. The ``rng``
        artifact input (present when the artifact samples) is NOT a
        per-instance feature — it is synthesized server-side from the
        request's integer ``seed`` (default 0), so clients never handle
        raw PRNG key data. With the scheduler on, the request instead
        rides the continuous-batching engine (per-request sampling
        knobs in the payload; see :meth:`_generate_scheduled`)."""
        if self.servable.meta.get("kind") != "generator":
            raise ValueError(
                "this artifact is not a generator — POST to :predict "
                "(export with export_generator for a decode artifact)")
        if self.engine is not None:
            return self._generate_scheduled(payload, request_id, trace)
        # engine-only payload knobs must not be silently ignored: the
        # monolithic program cannot truncate on stop_sequences or
        # speculate, and a 200 that quietly dropped the contract is
        # worse than a clear 400
        for knob in ("stop_sequences", "spec_tokens"):
            if payload.get(knob) is not None:
                raise ValueError(
                    f"{knob!r} requires the continuous-batching "
                    "scheduler (this server runs scheduler='off'; the "
                    "monolithic decode program cannot honor it) — "
                    "serve stepwise artifacts with scheduler on/auto")
        self._check_prompt_lengths(payload)
        sig = {k: v for k, v in self.servable.input_signature.items()
               if k != "rng"}
        feats, n = self._feature_arrays(payload, sig)
        pm = feats.get("prompt_mask")
        if pm is not None and not np.all(np.sum(pm != 0, axis=1) > 0):
            # an all-masked row would prefill over an empty key set and
            # return arbitrary tokens with a 200 (generate's own check
            # can't run — the mask is traced inside the exported
            # program); the server holds the concrete mask, so it rejects
            raise ValueError(
                "every prompt_mask row needs at least one real token")
        if "rng" in self.servable.input_signature:
            import jax
            seed = payload.get("seed", 0)
            # bool is an int subclass (true would silently mean seed 1),
            # and an out-of-int64 value would blow up as OverflowError
            # inside jax.random.key — a 500 for what is client input
            if isinstance(seed, bool) or not isinstance(seed, int) \
                    or not -(2 ** 63) <= seed < 2 ** 63:
                raise ValueError(
                    f"'seed' must be an int64-range integer, got "
                    f"{seed!r}")
            # build the key under the PRNG impl the artifact was traced
            # with (recorded at export since round 6); an artifact
            # exported under e.g. rbg takes [4]-shaped uint32 key data,
            # not threefry's [2] — the serve-time default impl is NOT
            # part of the artifact's contract. Validate the synthesized
            # data against the recorded rng signature so any residual
            # mismatch (older artifact + non-default server impl) is a
            # clear 4xx, not an opaque executable 500 (ADVICE r5).
            impl = self.servable.meta.get("prng_impl")
            try:
                key = (jax.random.key(seed, impl=impl) if impl
                       else jax.random.key(seed))
            except (ValueError, TypeError) as e:
                raise _ServerFault(
                    f"artifact metadata names unknown prng_impl "
                    f"{impl!r}: {e}") from e
            data = np.asarray(jax.random.key_data(key))
            spec = self.servable.input_signature["rng"]
            want = tuple(spec["shape"])
            if data.shape != want or str(data.dtype) != spec["dtype"]:
                raise ValueError(
                    f"cannot synthesize 'rng' for this artifact: the "
                    f"server PRNG impl {impl or 'default'!r} yields key "
                    f"data {data.shape} {data.dtype}, the artifact was "
                    f"exported expecting {want} {spec['dtype']} — "
                    "re-export with a matching jax_default_prng_impl "
                    "(new exports record prng_impl in export.json)")
            feats["rng"] = data
        toks = self._execute(feats)
        return {"generations": toks[:n].tolist()}

    def _make_handler(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            # a malformed Content-Length larger than the body would
            # otherwise block rfile.read forever, pinning the handler
            # thread for the client connection's lifetime
            timeout = 30

            def log_message(self, *a):      # quiet: tests/CLI own stdout
                pass

            def _send(self, code: int, obj: dict,
                      headers: dict | None = None) -> None:
                body = json.dumps(obj).encode()
                server._c_http_requests.inc()
                if code >= 400:
                    server._c_http_errors.inc()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _send_text(self, code: int, text: str,
                           content_type: str) -> None:
                body = text.encode()
                server._c_http_requests.inc()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == f"/v1/models/{server.name}":
                    self._send(200, {"model_version_status": [{
                        "version": "1", "state": "AVAILABLE",
                        "status": {"error_code": "OK",
                                   "error_message": ""}}]})
                elif self.path in ("/stats",
                                   f"/v1/models/{server.name}/stats"):
                    self._send(200, server.stats())
                elif self.path in ("/stats/history",
                                   f"/v1/models/{server.name}"
                                   "/stats/history"):
                    # the metric time-series ring (+ a fresh sample)
                    # for servetop and the router's fleet rollup
                    self._send(200, server.stats_history())
                elif self.path in ("/metrics",
                                   f"/v1/models/{server.name}/metrics"):
                    self._send_text(200, server.metrics_text(),
                                    obs_prom.CONTENT_TYPE)
                elif self.path in ("/healthz",
                                   f"/v1/models/{server.name}/healthz"):
                    # 200 ONLY while live: a wedged or dead scheduler
                    # thread must fail load-balancer probes instead of
                    # blackholing traffic behind a listening socket
                    h = server.health()
                    self._send(200 if h["status"] == "live" else 503, h)
                elif self.path in ("/trace/export",
                                   f"/v1/models/{server.name}"
                                   "/trace/export"):
                    # per-replica span drain for the fleet stitcher
                    self._send(200, server.trace_export())
                else:
                    self._send(404, {"error": f"unknown path {self.path}"})

            def do_POST(self):
                if self.path == "/trace/start":
                    self._send(200, server.trace_start())
                    return
                if self.path == "/trace/stop":
                    self._send(200, server.trace_stop())
                    return
                if self.path.startswith("/cancel/"):
                    rid = self.path[len("/cancel/"):]
                    if server.cancel(rid):
                        self._send(200, {"cancelled": rid})
                    else:
                        self._send(404, {
                            "error": f"no queued or live request "
                                     f"{rid!r} (already retired, or "
                                     "never submitted)"})
                    return
                routes = {f"/v1/models/{server.name}:predict":
                          server.predict,
                          f"/v1/models/{server.name}:generate":
                          server.generate}
                route = routes.get(self.path)
                if route is None:
                    self._send(404, {"error": f"unknown path {self.path}"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    if n > 1 << 30:
                        self._send(413, {"error": "request too large"})
                        return
                    # chaos seam: a dropped/garbled request body (inert
                    # single None-check without a registry installed)
                    faults.inject("http.read", detail=self.path)
                    body = self.rfile.read(n)
                    if len(body) != n:
                        self._send(400, {"error": "truncated body"})
                        return
                    payload = json.loads(body or b"{}")
                except (ValueError, TimeoutError, OSError) as e:
                    self._send(400, {"error": f"bad request: {e}"})
                    return
                try:
                    self._send(200, route(
                        payload,
                        self.headers.get("X-Request-Id") or None,
                        obs_trace.parse_traceparent(
                            self.headers.get("traceparent"))))
                except QueueFullError as e:
                    # bounded admission: tell the client WHEN to come
                    # back instead of silently stacking handler threads
                    self._send(429, {"error": str(e)},
                               headers={"Retry-After":
                                        str(int(e.retry_after + 0.5))})
                except DrainingError as e:
                    # graceful shutdown in progress: in-flight requests
                    # are finishing, new ones belong on another replica
                    self._send(503, {"error": str(e)},
                               headers={"Retry-After":
                                        str(int(e.retry_after + 0.5))})
                except DeadlineExceededError as e:
                    # the request's own deadline_ms budget expired; its
                    # slot and cache blocks are already back in the pool
                    self._send(504, {"error": str(e)})
                except RequestCancelledError as e:
                    # cancelled out from under its waiter (POST /cancel)
                    self._send(409, {"error": str(e)})
                except _ServerFault as e:               # executable died:
                    # platform mismatch, runtime OOM, ... must be a 500,
                    # not a dropped connection or a client-blaming 400
                    # (predict/generate wrap execution so even a
                    # ValueError from the runtime stays a server fault)
                    self._send(500, {"error": str(e)})
                except (ValueError, KeyError, TypeError) as e:
                    self._send(400, {"error": str(e)})  # client's fault
                except Exception as e:
                    self._send(500, {"error": f"{type(e).__name__}: {e}"})

        return Handler

    # -- lifecycle ------------------------------------------------------
    def serve(self) -> None:
        """Blocking serve loop (the CLI path); Ctrl-C stops cleanly."""
        if self._sampler is not None:
            self._sampler.start()
        try:
            self._httpd.serve_forever()
        except KeyboardInterrupt:
            self.stop()

    def start(self) -> "PredictServer":
        if self._sampler is not None:
            # first capture lands immediately: a just-started server
            # already holds its zero baseline, so the first window
            # delta covers the server's whole life
            self._sampler.start()
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="predict-server",
                                        daemon=True)
        self._thread.start()
        return self

    def _metrics_snapshot(self) -> dict:
        """The one atomic registry snapshot both /stats and /metrics
        render — freshened gauges included (engine/batcher share
        ``self.registry``, so either's snapshot covers everything)."""
        if self.engine is not None:
            return self.engine.metrics_snapshot()
        if self.batcher is not None:
            return self.batcher.metrics_snapshot()
        return self.registry.snapshot()

    def metrics_text(self) -> str:
        """``GET /metrics``: Prometheus text exposition."""
        return obs_prom.render(self._metrics_snapshot())

    def _on_history_sample(self, sampler) -> None:
        """Runs after every CADENCE ring capture; ``GET
        /stats/history`` polls evaluate separately over ring + their
        ephemeral freshness sample."""
        self._evaluate_slo(sampler.history())

    def _evaluate_slo(self, history) -> list[dict] | None:
        """Evaluate the objectives over ``history``, publish the
        results for ``/healthz``/``/stats/history``, and turn a
        multi-window burn breach into a rate-limited ``slo_burn``
        incident bundle carrying the offending objectives and the
        history tail. Never raises into a caller (the sampler already
        guards, but a burn evaluator that could kill sampling would
        blind exactly the incident it exists to evidence)."""
        try:
            results = obs_slo.evaluate(
                history, self._slo_objectives,
                fast_s=self.slo_fast_window_s,
                slow_s=self.slo_slow_window_s,
                threshold=self.slo_burn_threshold)
        except Exception as e:          # noqa: BLE001 — see docstring
            from .utils.logging import get_logger
            get_logger("serving").warning("slo evaluation failed: %s",
                                          e)
            return None
        with self._slo_lock:
            self._slo_results = results
        breaching = [r for r in results if r["breach"]]
        if breaching and self._flightrec is not None:
            worst = max(breaching, key=lambda r: r["burn_fast"])
            tail = list(history)[-8:]
            self._flightrec.incident(
                "slo_burn",
                detail=(f"{worst['class']}:{worst['kind']} burning "
                        f"{worst['burn_fast']}x fast / "
                        f"{worst['burn_slow']}x slow (goal "
                        f"{worst['goal']}, attainment "
                        f"{worst['attainment']})"),
                extra={"slo": results,
                       "slo_windows": {
                           "fast_s": self.slo_fast_window_s,
                           "slow_s": self.slo_slow_window_s,
                           "threshold": self.slo_burn_threshold},
                       "history_tail": [[t, snap] for t, snap in tail]})
        return results

    def stats_history(self) -> dict:
        """``GET /stats/history``: the time-series ring as JSON —
        ``[t, snapshot]`` samples (t in this process's perf_counter
        clock; ``clock`` rides beside them so the router's rollup can
        align), the declared objectives, and the latest burn-rate
        results. The poll appends an EPHEMERAL fresh sample (and
        evaluates the objectives over ring + it, so breach checks are
        always current), but the ring itself stores only cadence
        samples — concurrent pollers can never erode its time
        coverage below the burn windows it was sized for. Sampler
        off: ``{"enabled": false}`` with empty samples — a 200, so
        fleet scrapes degrade gracefully."""
        if self._sampler is None:
            return {"enabled": False, "process": self.process_name,
                    "clock": time.perf_counter(), "samples": [],
                    "slo": None}
        history = self._sampler.history() + [self._sampler.peek()]
        results = self._evaluate_slo(history)
        if results is None:
            with self._slo_lock:
                results = self._slo_results
        return obs_ts.to_payload(
            history,
            enabled=True,
            process=self.process_name,
            clock=time.perf_counter(),
            interval_s=self._sampler.interval_s,
            max_samples=self._sampler.max_samples,
            slo={"objectives": [o.to_dict()
                                for o in self._slo_objectives],
                 "results": results,
                 "fast_window_s": self.slo_fast_window_s,
                 "slow_window_s": self.slo_slow_window_s,
                 "burn_threshold": self.slo_burn_threshold})

    def trace_start(self) -> dict:
        """``POST /trace/start``: arm the span recorder (clears any
        previous capture)."""
        rec = obs_trace.recorder()
        rec.start()
        return {"tracing": True, "max_events": rec.max_events}

    def trace_stop(self) -> dict:
        """``POST /trace/stop``: disarm and return the capture as
        chrome://tracing / Perfetto trace-event JSON."""
        rec = obs_trace.recorder()
        rec.stop()
        return rec.to_chrome()

    def trace_export(self) -> dict:
        """``GET /trace/export``: DRAIN this server's spans (its own
        process label only — N in-process replicas share one ring) as
        JSON for the fleet stitcher, with the local monotonic clock
        beside them so the router's offset estimate has an anchor.
        ``events_dropped`` is the RING's count: per-process drop
        attribution is not tracked, so in-process fleets (shared ring)
        over-report it per export — the stitched metadata's sum is
        exact only for the production one-ring-per-process shape."""
        rec = obs_trace.recorder()
        spans = rec.drain(process=self.process_name)
        return {"process": self.process_name,
                "clock": time.perf_counter(),
                "spans": [[p, lane, name, t0, t1, args]
                          for p, lane, name, t0, t1, args in spans],
                "events_dropped": rec.events_dropped,
                "enabled": rec.enabled}

    def health(self) -> dict:
        """``GET /healthz``: the engine's watchdog view (live / stalled
        / dead with the heartbeat age), plus ``mono_now`` (this
        process's ``perf_counter``) — the clock sample the router's
        per-replica offset estimation reads off every probe. A stalled
        watchdog also fires the flight recorder (cause
        ``watchdog_stall``, rate-limited): the probe that demotes the
        replica is the incident's own evidence, no arming required.
        Without a scheduler thread to watch (scheduler off, or a
        predict artifact) the server answering at all IS the liveness
        signal."""
        if self.engine is not None:
            h = self.engine.health()
            if h["status"] == "stalled" and self._flightrec is not None:
                self._flightrec.incident(
                    "watchdog_stall",
                    detail=f"heartbeat {h['heartbeat_age_s']}s old "
                           f"(stall_after_s {h['stall_after_s']})",
                    extra={"health": h})
        else:
            h = {"status": "live", "scheduler": self.scheduler}
        h["mono_now"] = time.perf_counter()
        if self._sampler is not None:
            # ADVISORY only — burn is an operator page, not a
            # load-balancer signal, so it never changes the status
            # code (a breaching-but-live replica still takes traffic)
            with self._slo_lock:
                results = self._slo_results
            if results is not None:
                h["slo"] = obs_slo.summarize(results)
        return h

    def cancel(self, request_id: str) -> bool:
        """``POST /cancel/<request_id>``: cancel a queued or live
        :generate request. False (→ 404) when the id is unknown,
        already retired, or there is no engine to cancel against."""
        if self.engine is None:
            return False
        return self.engine.cancel(request_id)

    def stats(self) -> dict:
        """The /stats payload: scheduler mode plus per-scheduler
        counters (the generate block's ``decode_steps`` /
        ``steps_shared`` are the continuous-batching invariant's
        observable — K concurrent requests should cost ~max(max_new)
        decode dispatches, not the per-request sum). Every counter is
        a view of the SAME registry snapshot /metrics renders."""
        out: dict[str, Any] = {"model": self.name,
                               "scheduler": self.scheduler}
        snap = self._metrics_snapshot()
        if self.engine is not None:
            out["generate"] = self.engine.stats(snap)
        if self.batcher is not None:
            out["predict"] = self.batcher.stats(snap)
        return out

    def stop(self, drain: bool = True) -> None:
        """Shut down. ``drain=True`` (default, and the SIGTERM path) is
        graceful: the engine stops admitting (new ``:generate`` answer
        503 + Retry-After — the HTTP listener stays up to say so),
        queued/in-flight requests finish under ``drain_timeout_s``, the
        request log flushes, THEN the listener closes. ``drain=False``
        is fail-fast: listener down first, queued/live requests failed
        loudly. Both raise :class:`~.serving_batch.EngineStalledError`
        when the scheduler thread never parks."""
        if self._sampler is not None:
            self._sampler.stop()
        try:
            if self.engine is not None and drain:
                self.engine.drain()
        finally:
            # the listener comes down even when drain() raises
            # EngineStalledError — otherwise a wedged scheduler would
            # leave the socket up refusing everything and SIGTERM
            # would never actually stop the process
            self._httpd.shutdown()
            self._httpd.server_close()
            if self._thread is not None:
                self._thread.join(timeout=5)
            if self.engine is not None and not drain:
                self.engine.close()
            if self.batcher is not None:
                self.batcher.close()
            if self._request_logger is not None:
                self._request_logger.close()

    def kill(self) -> None:
        """Simulate a process crash (the fleet chaos harness's
        ``replica.crash`` seam): the listener is torn down NOW, the
        scheduler/batcher failed fast — no drain, no request-log
        flush, queued and live requests die loudly. Unlike
        :meth:`stop`, a wedged scheduler is tolerated silently: a real
        crash takes the wedged thread with it, so raising
        ``EngineStalledError`` here would make the simulated crash
        LESS abrupt than the real one."""
        if self._sampler is not None:
            self._sampler.stop()
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        try:
            if self.engine is not None:
                self.engine.close(timeout=5)
            if self.batcher is not None:
                self.batcher.close(timeout=5)
        except EngineStalledError:
            pass
        if self._request_logger is not None:
            self._request_logger.close()

    def __enter__(self) -> "PredictServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def main(argv=None) -> int:
    """``python -m distributed_tensorflow_example_tpu.serving_http
    --export_dir D [--port P]`` — serve until interrupted."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--export_dir", required=True)
    ap.add_argument("--name", default=None)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8501)
    ap.add_argument("--scheduler", choices=("auto", "on", "off"),
                    default="auto",
                    help="continuous batching / micro-batching (auto = "
                    "on when the artifact has stepwise generator "
                    "programs); off = the single-flight parity path")
    ap.add_argument("--batch_max_size", type=int, default=8,
                    help=":predict micro-batch row cap")
    ap.add_argument("--batch_max_wait_ms", type=float, default=5.0,
                    help=":predict admission window per micro-batch")
    ap.add_argument("--max_queue", type=int, default=64,
                    help="admission queue bound (full -> 429)")
    ap.add_argument("--prefix_cache", choices=("on", "off"),
                    default="on",
                    help="paged artifacts only: shared-prefix block "
                    "reuse at admission (off = every prompt prefills "
                    "cold — the shared-vs-cold parity tool)")
    ap.add_argument("--metrics", choices=("on", "off"), default="on",
                    help="telemetry registry behind GET /metrics and "
                    "/stats (off = every counter increment reduces to "
                    "one branch; /stats serves zeros)")
    ap.add_argument("--trace_buffer_events", type=int, default=65536,
                    help="span ring-buffer bound for POST /trace/start"
                    " captures (oldest events drop first)")
    ap.add_argument("--request_log", default=None,
                    help="append one JSONL event per retired :generate "
                    "request (request_id + queue/prefill/decode ms) "
                    "to this path")
    ap.add_argument("--thread_sanitizer", action="store_true",
                    help="debug: assert the scheduler thread-ownership "
                    "discipline on every guarded engine attribute "
                    "access (a foreign-thread touch raises "
                    "ThreadOwnershipError naming the field and thread; "
                    "off = the engine class is untouched)")
    ap.add_argument("--default_deadline_ms", type=int, default=0,
                    help="latency budget applied to :generate requests "
                    "that carry no deadline_ms of their own (0 = none); "
                    "expiry retires the slot between steps, frees its "
                    "cache blocks, and answers 504")
    ap.add_argument("--drain_timeout_s", type=float, default=30.0,
                    help="graceful-drain budget on SIGTERM/stop(): new "
                    "admissions 503 while queued/in-flight requests "
                    "finish; a scheduler thread still running past the "
                    "budget raises EngineStalledError")
    ap.add_argument("--spec_tokens", type=int, default=0,
                    help="speculative decoding: verify up to K-1 "
                    "self-drafted tokens per shared dispatch (needs an "
                    "artifact exported with export_generator(..., "
                    "spec_tokens=K); auto-off with a warning when the "
                    "artifact lacks the verify program). Greedy output "
                    "stays byte-identical; 0 = off (bitwise no-op). "
                    "Per-request `spec_tokens` in the payload opts out "
                    "(0) or caps lower")
    ap.add_argument("--prefill_chunk_tokens", type=int, default=0,
                    help="chunked prefill: feed cold prompts to the "
                    "engine in block-aligned chunks of this many "
                    "tokens per scheduler iteration, interleaved with "
                    "shared decode steps, so a long prompt can never "
                    "stall live decoders for a whole monolithic "
                    "prefill (needs an artifact exported with "
                    "export_generator(..., prefill_chunk=C); auto-off "
                    "with a warning when the artifact lacks the chunk "
                    "program). Greedy bytes stay byte-identical; 0 = "
                    "off (bitwise no-op)")
    ap.add_argument("--default_priority",
                    choices=("interactive", "batch", "best_effort"),
                    default="interactive",
                    help="admission class for :generate requests that "
                    "carry no 'priority' of their own — orders the "
                    "queue (class, then earliest deadline, then FIFO, "
                    "with aging so best_effort never starves) and "
                    "names the brownout rung that sheds the request "
                    "under overload")
    ap.add_argument("--shed_policy", choices=("auto", "off"),
                    default="auto",
                    help="graceful load shedding: 'auto' runs the "
                    "pressure ladder (healthy -> shed_best_effort -> "
                    "shed_batch -> interactive_only; 429 + measured "
                    "Retry-After per shed class) plus the deadline-"
                    "feasibility shed (a queued request that can no "
                    "longer meet its deadline_ms is 429'd immediately "
                    "instead of 504ing later); 'off' keeps only the "
                    "blunt queue-full 429")
    ap.add_argument("--stall_after_s", type=float, default=10.0,
                    help="GET /healthz reports 'stalled' (503) once the "
                    "scheduler heartbeat is older than this")
    ap.add_argument("--flight_recorder", choices=("on", "off"),
                    default="on",
                    help="always-on span ring + auto incident bundles "
                    "(on, the default: the bounded ring records "
                    "without POST /trace/start so failures have "
                    "history; off: byte- and dispatch-identical "
                    "serving with the ring armed on demand only)")
    ap.add_argument("--history_interval_s", type=float, default=0.0,
                    help="metric time-series: capture the registry "
                    "snapshot into a bounded ring every this many "
                    "seconds, served by GET /stats/history (rates, "
                    "window quantiles, SLO burn — the servetop feed); "
                    "0 = off, a provable no-op (the sampler is a pure "
                    "registry reader on its own thread)")
    ap.add_argument("--history_samples", type=int, default=600,
                    help="history ring bound (oldest samples drop "
                    "first); size it to cover the slow burn window: "
                    "samples >= slow_window_s / history_interval_s")
    ap.add_argument("--slo_spec", default=None,
                    help="per-class objectives, 'class:kind=target"
                    "[@goal]' joined with ';' — kinds: hit_rate "
                    "(deadline hit rate; =X is the goal), p95_ms "
                    "(latency bound in ms, @goal default 0.95), "
                    "availability (class 'all' only). Example: "
                    "'interactive:p95_ms=250@0.95;interactive:"
                    "hit_rate=0.99;all:availability=0.999'. Needs "
                    "--history_interval_s; unset = the default "
                    "objective set")
    ap.add_argument("--incident_dir", default=None,
                    help="directory for flight-recorder incident "
                    "bundles (engine-fatal rebuild, watchdog stall, "
                    "poison eviction), one timestamped JSON per "
                    "incident, rate-limited per cause; unset = no "
                    "bundles are written even with the recorder on")
    ap.add_argument("--fault_spec", default=None,
                    help="arm the serving fault seams (engine.prefill / "
                    "engine.decode_step / engine.admit / pool.alloc / "
                    "http.read) with this ;-separated rule spec — chaos "
                    "drills only; unset = every seam is an inert None-"
                    "check")
    ap.add_argument("--fault_seed", type=int, default=0,
                    help="seed for p= fault rules in --fault_spec")
    args = ap.parse_args(argv)
    enable_compilation_cache()
    if args.fault_spec:
        faults.install(faults.parse_spec(args.fault_spec,
                                         seed=args.fault_seed))
    srv = PredictServer(args.export_dir, name=args.name, host=args.host,
                        port=args.port, scheduler=args.scheduler,
                        batch_max_size=args.batch_max_size,
                        batch_max_wait_ms=args.batch_max_wait_ms,
                        max_queue=args.max_queue,
                        prefix_cache=args.prefix_cache == "on",
                        metrics=args.metrics == "on",
                        trace_buffer_events=args.trace_buffer_events,
                        request_log=args.request_log,
                        thread_sanitizer=args.thread_sanitizer,
                        default_deadline_ms=args.default_deadline_ms,
                        drain_timeout_s=args.drain_timeout_s,
                        stall_after_s=args.stall_after_s,
                        spec_tokens=args.spec_tokens,
                        prefill_chunk_tokens=args.prefill_chunk_tokens,
                        default_priority=args.default_priority,
                        shed_policy=args.shed_policy,
                        flight_recorder=args.flight_recorder == "on",
                        incident_dir=args.incident_dir,
                        history_interval_s=args.history_interval_s,
                        history_samples=args.history_samples,
                        slo_spec=args.slo_spec)

    def _graceful(signum, frame):
        # stop() must run off the serve_forever thread (shutdown()
        # called from inside the loop would deadlock); the drain keeps
        # the listener up answering 503 until in-flight work finishes
        threading.Thread(target=srv.stop, name="sigterm-drain",
                         daemon=True).start()

    import signal
    signal.signal(signal.SIGTERM, _graceful)
    print(f"serving {srv.name!r} on http://{args.host}:{srv.port}"
          f"/v1/models/{srv.name}:predict", flush=True)
    srv.serve()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())

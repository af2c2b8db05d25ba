"""Traffic kind ``serve``: the program's paged continuous-batching server
under a seeded request schedule, open or closed loop.

Set-up makes the weights from the seed, exports the generator programs
(``serving.export_generator``: ragged, stepwise, paged), starts an
in-process ``serving_http.PredictServer`` and sends one warm request, so
that exactly the prefill and decode programs the window drives are
compiled. The load generator is a child process (benchmark/loadgen.py)
that posts ``:generate`` over real HTTP; traffic starts ``ramp_s`` before
the window and, open loop, goes on after it until every measured request
has returned.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=1200) as r:
        return json.loads(r.read())


def start_server(env, t: dict):
    """Weights, export, server, warm request. Returns the server, its
    ``:generate`` URL and the set-up spans."""
    import jax

    from benchmark import program, weights
    from distributed_tensorflow_example_tpu.serving import export_generator
    from distributed_tensorflow_example_tpu.serving_http import (
        PredictServer)

    cfg = program.train_config(env)
    model, ref, ref_cfg, spec = program.build_model(env, cfg)
    params = weights.make_params(spec, env.seed)
    program.check_tree(jax.eval_shape(model.init, jax.random.key(0)), params)
    env.mark("seeded weights")
    e = env.pick(t, "engine")
    export_dir = os.path.join(env.workdir, "export")
    t0 = time.perf_counter()
    export_generator(model, params, export_dir, ragged=True, stepwise=True,
                     paged=True, slots=e["slots"],
                     block_size=e["block_size"], prompt_len=e["prompt_len"],
                     max_new_tokens=e["max_new_tokens"],
                     platforms=tuple(e["platforms"]))
    export_s = time.perf_counter() - t0
    env.mark("export")
    del params
    srv = PredictServer(export_dir, port=0, max_queue=e["max_queue"])
    srv.start()
    url = f"http://127.0.0.1:{srv.port}/v1/models/{srv.name}:generate"
    t0 = time.perf_counter()
    # one warm request compiles all the window drives: the prefill and the
    # decode program, and the copy-on-write block copy, which the engine
    # jits at the first decode write into a block the prefix cache shares:
    # so the prompt must not end on a block boundary
    rs = np.random.RandomState(0)
    warm_len = e["prompt_len"] - e["block_size"] // 2
    warm = rs.randint(110, ref_cfg["vocab_size"], warm_len).tolist()
    _post(url, {"inputs": {"input_ids": [warm]}, "max_new": 4})
    if not srv.engine.stats()["cow_copies"]:
        raise RuntimeError("the warm request did not reach the engine's "
                           "copy-on-write program: it would compile "
                           "inside the window")
    compile_s = time.perf_counter() - t0
    env.mark("warm request (compiles prefill and decode)")
    return srv, url, ref, ref_cfg, spec, {"export_s": export_s,
                                          "serve_compile_s": compile_s}


def make_plan(env, t: dict, url: str, vocab: int) -> dict:
    from benchmark import datagen
    ramp, window = float(env.pick(t, "ramp_s")), env.window_seconds
    lo, hi = ramp, ramp + window
    drain = float(env.pick(t, "drain_cap_s"))
    if t["loop"] == "open":
        return {"url": url, "mode": "open", "window": [lo, hi],
                "stop_s": hi + drain,
                "requests": datagen.open_schedule(
                    env.pick(t, "mix"), vocab, env.seed,
                    [ramp, window, drain])}
    mix = env.pick(t, "mix")
    count = int(mix["clients"] * mix["requests_per_client"])
    return {"url": url, "mode": "closed", "window": [lo, hi],
            "stop_s": hi + drain,
            "clients": datagen.closed_schedule(mix, vocab, env.seed, count)}


def drive(env, srv, plan: dict) -> tuple[dict, dict]:
    """Run the load generator child over ``plan``; snapshot the engine's
    counters at the window's edges (and trace inside it)."""
    plan_path = os.path.join(env.workdir, "plan.json")
    out_path = os.path.join(env.workdir, "results.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"),
         plan_path, out_path], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True)
    try:
        if child.stdout.readline().strip() != "ready":
            raise RuntimeError("the load generator did not come up")
        t0 = time.perf_counter() + 0.5
        child.stdin.write(f"{t0!r}\n")
        child.stdin.flush()
        lo, hi = plan["window"]
        edges = {}
        time.sleep(max(0.0, t0 + lo - time.perf_counter()))
        edges["open"] = (time.perf_counter(), srv.engine.stats())
        if env.trace:
            env.start_trace()
            time.sleep(max(0.0, min(env.trace_seconds, hi - lo)))
            env.stop_trace()
        time.sleep(max(0.0, t0 + hi - time.perf_counter()))
        edges["close"] = (time.perf_counter(), srv.engine.stats())
        child.wait(timeout=plan["stop_s"] - hi + 60.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    with open(out_path) as f:
        out = json.load(f)
    out["t0"] = t0
    return out, edges


def check_outputs(env, ref, ref_cfg, spec, finished: list[dict], t: dict,
                  precision: str = "f32", control: bool = False) -> dict:
    """The plain reference, once over each sampled prompt with its served
    tokens: the widest gap by which a served token's logit lies below the
    reference's best. ``control``: the gap of the token the lower
    ``precision`` puts first, at the same positions."""
    import jax
    import jax.numpy as jnp

    from benchmark import datagen, weights
    e = env.pick(t, "engine")
    width = e["prompt_len"] + e["max_new_tokens"]
    n = min(int(env.pick(t, "check_requests")), len(finished))
    order = sorted(finished, key=lambda r: -(r["prompt_len"]
                                             + len(r["tokens"])))
    rest = order[1:]
    datagen.rng(env.seed, 5).shuffle(rest)
    sample = [order[0], *rest[:n - 1]]       # the longest is always in it
    by_idx = env.requests_by_idx
    ids = np.zeros((len(sample), width), np.int32)
    mask = np.zeros((len(sample), width), np.int32)
    for i, r in enumerate(sample):
        seq = by_idx[r["idx"]]["prompt"] + r["tokens"]
        ids[i, :len(seq)] = seq
        mask[i, :len(seq)] = 1
    params = weights.make_params(spec, env.seed)
    fwd = jax.jit(lambda p, x, m, prec: ref.logits(ref_cfg, p, x, m, prec),
                  static_argnums=3)
    widest, served_tokens = 0.0, 0
    for i, r in enumerate(sample):          # one row at a time: it fits
        x, m = jnp.asarray(ids[i:i + 1]), jnp.asarray(mask[i:i + 1])
        best = np.asarray(fwd(params, x, m, "f32")[0])
        p, k = r["prompt_len"], len(r["tokens"])
        rows = best[p - 1:p - 1 + k]
        if control:
            low = np.asarray(fwd(params, x, m, precision)[0])
            chosen = np.argmax(low[p - 1:p - 1 + k], axis=-1)
        else:
            chosen = np.asarray(r["tokens"])
        gaps = rows.max(axis=-1) - rows[np.arange(k), chosen]
        widest = max(widest, float(gaps.max()))
        served_tokens += k
    return {"served_logit_gap": widest, "_tokens": served_tokens,
            "_requests": len(sample)}


def measure(plan: dict, out: dict) -> dict:
    """The load generator's results -> the cell's end-to-end values, what
    the window attempted, and the per-layer numbers read on the way."""
    from benchmark import stats
    lo, hi = plan["window"]
    window = hi - lo
    results = out["results"]
    ok = [r for r in results if r.get("status") == 200]
    values, ctxv = {}, {}
    if plan["mode"] == "closed":
        # every request that overlaps the window, with the share of its
        # tokens that falls inside: a request's tokens spread evenly over
        # the time the client waited for it. Counting only requests that
        # COMPLETE inside would credit whole requests at one edge and drop
        # them at the other: +-1.6 % of noise at 64 slots
        over = [r for r in results if r["sent_s"] < hi
                and r.get("done_s", hi) > lo]
        finished = [r for r in ok if lo <= r["done_s"] < hi]
        attempted = len(over)
        failed = sum(1 for r in over if r.get("status") != 200)
        inside = 0.0
        for r in over:
            if r.get("status") == 200:
                share = (min(r["done_s"], hi) - max(r["sent_s"], lo)) / (
                    r["done_s"] - r["sent_s"])
                inside += len(r["tokens"]) * share
        values["serve_tokens_per_s"] = inside / window
        ctxv["completed_tokens_per_s"] = sum(
            len(r["tokens"]) for r in finished) / window
    else:
        due = [r for r in plan["requests"] if lo <= r["due_s"] < hi]
        got = {r["idx"]: r for r in results}
        lat, late, finished = [], [], []
        for q in due:
            r = got.get(q["idx"])
            if r is None or r.get("status") != 200:
                lat.append(1e3 * (out["ended_s"] - q["due_s"]))
                continue
            lat.append(1e3 * (r["done_s"] - r["due_s"]))
            late.append(1e3 * (r["sent_s"] - r["due_s"]))
            finished.append(r)
        attempted, failed = len(due), len(due) - len(finished)
        values["req_latency_p50_ms"] = stats.percentile(lat, 50)
        values["req_latency_p95_ms"] = stats.percentile(lat, 95)
        ctxv["loadgen_late_ms_p95"] = stats.percentile(late, 95)
        ctxv["queue_ms_p95"] = stats.percentile(
            [r["timings"]["queue_ms"] for r in finished], 95)
        ctxv["offered_tokens_per_s"] = sum(
            len(r["tokens"]) for r in finished) / window
        ctxv["samples_beyond_p95"] = stats.samples_beyond(len(lat), 95)
    return {"values": values, "ctxv": ctxv, "finished": finished,
            "attempted": attempted, "failed": failed, "ok": ok}


def run(env) -> dict:
    from benchmark import stats
    t = env.traffic
    srv, url, ref, ref_cfg, spec, spans = start_server(env, t)
    try:
        plan = make_plan(env, t, url, ref_cfg["vocab_size"])
        every = (plan["requests"] if plan["mode"] == "open"
                 else [r for q in plan["clients"] for r in q])
        env.requests_by_idx = {r["idx"]: r for r in every}
        env.break_program(server=srv)       # tests only: a no-op in a run
        setup_s = time.perf_counter() - env.t_process
        out, edges = drive(env, srv, plan)
        peak = env.memory_peak_bytes()
    finally:
        srv.stop(drain=False)
    del srv
    m = measure(plan, out)
    values, finished = m["values"], m["finished"]
    attempted, failed, ok = m["attempted"], m["failed"], m["ok"]
    results, counts = out["results"], {}
    ctxv = dict(spans, **m["ctxv"])
    ctxv["http_overhead_ms_p50"] = stats.percentile(
        [1e3 * (r["done_s"] - r["sent_s"]) - r["timings"]["total_ms"]
         for r in finished], 50)
    (t_a, a), (t_b, b) = edges["open"], edges["close"]
    steps = b["decode_steps"] - a["decode_steps"]
    ctxv["decode_step_ms"] = 1e3 * (t_b - t_a) / max(1, steps)
    ctxv["slot_occupancy_pct"] = 100.0 * (
        b["decode_slot_steps"] - a["decode_slot_steps"]) / max(
            1, steps * a["slots"])
    counts.update(requests=attempted, finished=len(finished),
                  decode_steps=steps)
    # exact: every finished request carries exactly the tokens it asked for
    short = sum(1 for r in finished if len(r["tokens"]) != r["max_new"])
    compared = {"token_count_mismatches": short}
    t0 = time.perf_counter()
    checked = check_outputs(env, ref, ref_cfg, spec, finished, t)
    env.note(f"check: reference {time.perf_counter() - t0:.1f}s over "
             f"{checked.pop('_requests')} requests, "
             f"{checked.pop('_tokens')} served tokens; "
             f"{len(finished)} finished of {attempted} measured, "
             f"{len(results)} posted")
    compared.update(checked)
    env.finished = finished
    return {"attempted": attempted, "failed": failed, "compared": compared,
            "memory_peak_bytes": peak, "setup_s": setup_s,
            "counts": counts, "values": values,
            "record": {"spans": spans, "counts": counts, "per_layer": ctxv,
                       "requests": [
                           [r["idx"], r["due_s"], round(r["sent_s"], 4),
                            round(r["done_s"], 4), len(r["tokens"]),
                            r["timings"]["queue_ms"]] for r in ok],
                       "request_columns": ["idx", "due_s", "sent_s",
                                           "done_s", "tokens", "queue_ms"]},
            "ctx": {"values": ctxv, "memory_peak_bytes": peak}}


def control(env) -> dict:
    """The control: the reference in fp8 (the precision below the bf16 the
    configuration states) in the program's place. At each position of the
    same prompts and served tokens (those of the run this process just
    made, else sequences of the mix's sizes drawn from the seed) it reads
    the gap of the token fp8 puts first. No server runs for it."""
    from benchmark import datagen
    t = env.traffic
    ref = env.manifest.reference(env.config)
    ref_cfg = env.pick(env.config, "sizes") if env.rehearse else env.config
    spec = ref.param_spec(ref_cfg)
    finished = getattr(env, "finished", None)
    if not finished:
        n = int(env.pick(t, "check_requests"))
        reqs = datagen.serving_requests(
            env.pick(t, "mix"), ref_cfg["vocab_size"],
            datagen.rng(env.seed, 3), 8 * n)
        rs = datagen.rng(env.seed, 6)
        env.requests_by_idx = {r["idx"]: r for r in reqs}
        finished = [{"idx": r["idx"], "prompt_len": len(r["prompt"]),
                     "tokens": rs.randint(110, ref_cfg["vocab_size"],
                                          r["max_new"]).tolist()}
                    for r in reqs]
    out = check_outputs(env, ref, ref_cfg, spec, finished, t,
                        precision="fp8", control=True)
    return {"served_logit_gap": out["served_logit_gap"],
            "token_count_mismatches": 0}

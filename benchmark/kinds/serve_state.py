"""Traffic kind ``serve_state``: the paged server under a seeded request
schedule, for a decoder that keeps per-request recurrent state beside a
paged latent cache and generates one token a step.

The plan, the load generator and the window's arithmetic are
``kinds/serve.py``'s (``make_plan``, ``drive``, ``measure``) and
``loadgen.py``, unchanged. What differs:

- *weights*: a leaf at a time in the configuration's storage dtype, the
  recurrent decay's leaves from the family's ranges
  (``benchmark/weights_by_range.py``); the program's artifact takes them
  as an argument, the reference reads the same arrays;
- *the share*: the configuration's ``model_cfg`` (experts held, the
  vocabulary's slice) is set on the registry's model before it is
  exported; prompt ids are drawn from the held slice;
- *the order of the lengths*: ``kinds/serve.py``'s closed schedule deals
  the mix's fixed multiset of lengths by the seed, and a run of this cell
  posts a quarter of it (two of a client's eight requests), so the seed
  chose how many chunks each served token cost: 7 % of spread in
  ``serve_tokens_per_s`` where a run repeats to ~1 %
  (``same_work``, below). Here every seed posts the same lengths in the
  same order, and draws only the ids (and the weights);
- *the warm request*: a prompt over two chunks that ends inside the
  second, then a few tokens: the chunk program, the slot's zeroing and
  the decode step compile;
- *``correct``*: after the window the server is stopped and its memory
  freed. For a seeded sample of ``check_requests`` finished requests (the
  longest always among them) the reference runs once over prompt + served
  tokens (padded to ``WIDTH_STEP`` x 2^k: what follows a token is
  invisible to it), and each served token's logit is read against the
  reference's best at its position. Compared:
  ``served_logit_gap_mean`` (the mean gap over every checked token: what
  a lower precision moves), ``served_logit_gap_request_max`` (the largest
  of the checked requests' own mean gaps: under seeded random weights a
  recurrent state forgets within a few hundred tokens, so a state lost at
  a chunk boundary or left in a reused slot shows in the requests whose
  first served token follows it closely, and hardly in the run's mean.
  The sample is drawn by the seed among all that finished; a planted
  fault's among those it can still reach: ``benchmark/planted_state.py``)
  and,
  exactly, ``token_count_mismatches``. The WIDEST gap is read and printed
  (``read served_logit_gap``, the record's ``widest_logit_gap``) and
  compared only where the cell's limits name it: one expert chosen
  otherwise, which bfloat16 rounding does to a near-tie among 256 sigmoid
  scores, moves one token about as far as fp8 moves all of them.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmark.manifest import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
serve = load_module(os.path.join(HERE, "serve.py"))

#: the reference's forward is compiled once a width
WIDTH_STEP = 2048
#: a request's first served tokens (the least a request is given): what a
#: state wrong at the prompt's end moves most
HEAD = 128


#: the order of the lengths is drawn from this, in every run
ORDER_SEED = 0


def same_work(clients: list, group: int) -> list:
    """The plan's requests, their lengths dealt anew in an order that no
    seed moves: the plan's token ids, cut again.

    The multiset stays the generator's (``datagen.lognormal_grid``: the
    lognormal's evenly spaced quantiles). Sorted, it falls into ``group``
    strata, and each wave of ``group`` requests in posting order (a client's
    r-th request is posted after its r-1-th; clients post in their order)
    takes one length of every stratum, for prompts and for outputs apart,
    paired and placed by ``ORDER_SEED``. So whatever part of the schedule a
    run reaches costs what any other part costs, and costs every seed the
    same."""
    n, per = len(clients), len(clients[0])
    every = [clients[i % n][i // n] for i in range(n * per)]
    if n % group:
        group = n
    waves = n * per // group
    rs = np.random.RandomState(ORDER_SEED)

    def deal(lengths):
        strata = np.sort(lengths).reshape(group, waves)
        out = np.stack([rs.permutation(row) for row in strata], axis=1)
        return np.stack([rs.permutation(w) for w in out]).reshape(-1)

    prompt = deal([len(r["prompt"]) for r in every])
    new = deal([r["max_new"] for r in every])
    ids = np.concatenate([np.asarray(r["prompt"], np.int64) for r in every])
    ends = np.cumsum(prompt)
    reqs = [{"idx": i, "prompt": ids[e - p:e].tolist(), "max_new": int(k)}
            for i, (p, e, k) in enumerate(zip(prompt, ends, new))]
    return [reqs[c::n] for c in range(n)]


def _apply_share(env, model) -> None:
    for key, value in env.pick(env.config, "model_cfg").items():
        if not hasattr(model.cfg, key):
            raise RuntimeError(f"the program's description has no {key!r}")
        setattr(model.cfg, key, value)


def start_server(env, t: dict):
    """Weights, export, server, warm request."""
    import jax

    from benchmark import program, weights_by_range
    from distributed_tensorflow_example_tpu.serving import export_generator
    from distributed_tensorflow_example_tpu.serving_http import (
        PredictServer)

    cfg = program.train_config(env)
    model, ref, ref_cfg, spec = program.build_model(env, cfg)
    _apply_share(env, model)
    env.break_program(model=model)          # tests only: a no-op in a run
    params = weights_by_range.make_params(spec, env.seed, model.param_dtype)
    program.check_tree(jax.eval_shape(model.init, jax.random.key(0)), params)
    jax.block_until_ready(params)
    env.mark("seeded weights")
    e = env.pick(t, "engine")
    export_dir = os.path.join(env.workdir, "export")
    t0 = time.perf_counter()
    export_generator(model, params, export_dir, ragged=True, stepwise=True,
                     paged=True, slots=e["slots"],
                     block_size=e["block_size"], prompt_len=e["prompt_len"],
                     max_new_tokens=e["max_new_tokens"],
                     prefill_chunk=e["prefill_chunk"],
                     platforms=tuple(e["platforms"]))
    export_s = time.perf_counter() - t0
    env.mark("export")
    del params
    gc.collect()
    srv = PredictServer(export_dir, port=0, max_queue=e["max_queue"],
                        prefix_cache=False)
    srv.start()
    env.mark("server up (weights loaded)")
    url = f"http://127.0.0.1:{srv.port}/v1/models/{srv.name}:generate"
    t0 = time.perf_counter()
    rs = np.random.RandomState(0)
    warm_len = min(e["prompt_len"],
                   e["prefill_chunk"] + e["prefill_chunk"] // 2)
    warm = rs.randint(110, ref_cfg["vocab_size"], warm_len).tolist()
    serve._post(url, {"inputs": {"input_ids": [warm]}, "max_new": 4})
    st = srv.engine.stats()
    if st["prefill_chunks"] < 2 or st["decode_steps"] < 3:
        raise RuntimeError("the warm request did not ride the chunk program "
                           "twice and the decode step")
    compile_s = time.perf_counter() - t0
    env.mark("warm request (compiles chunk program, zeroing and decode)")
    return srv, url, ref, ref_cfg, spec, model.param_dtype, {
        "export_s": export_s, "serve_compile_s": compile_s}


def _free(srv) -> None:
    """The stopped server's device memory, given back now: handler threads
    of requests cut at the drain cap may hold the server a while longer,
    and the reference needs the room."""
    import jax
    eng = srv.engine
    for leaf in jax.tree_util.tree_leaves((eng._pool, eng.sw.params)):
        if not leaf.is_deleted():
            leaf.delete()


def check_outputs(env, ref, ref_cfg, spec, dtype, finished: list, t: dict,
                  precision: str = "f32", control: bool = False) -> dict:
    """The plain reference, once over each sampled prompt with its served
    tokens. ``control``: the gap of the token the lower ``precision`` puts
    first, at the same positions."""
    import jax
    import jax.numpy as jnp

    from benchmark import datagen, weights_by_range
    e = env.pick(t, "engine")
    n = min(int(env.pick(t, "check_requests")), len(finished))
    order = sorted(finished, key=lambda r: -(r["prompt_len"]
                                             + len(r["tokens"])))
    # the longest always; the rest by the seed, among all that finished. A
    # planted fault narrows them to the requests it can still reach
    # (``env.check_pool``: benchmark/planted_state.py)
    rest = list(getattr(env, "check_pool", list)(order[1:]))
    datagen.rng(env.seed, 5).shuffle(rest)
    sample = (order[:1] + rest)[:n]
    params = weights_by_range.make_params(spec, env.seed, dtype)
    kmax = e["max_new_tokens"]
    cap = -(-(e["prompt_len"] + 2 * kmax) // WIDTH_STEP) * WIDTH_STEP

    def rows(p, x, first, prec):
        # the served positions' hidden rows only: their logits, not
        # [width, vocabulary]
        hid = ref.hidden(ref_cfg, p, x, prec)
        return ref.head(ref_cfg, p, jax.lax.dynamic_slice_in_dim(
            hid, first, kmax, axis=0), prec)

    fwd = jax.jit(rows, static_argnums=3)
    gaps: list = []
    per_request = []
    for r in sample:
        prompt = env.requests_by_idx[r["idx"]]["prompt"]
        seq = list(prompt) + list(r["tokens"])
        p, k = len(prompt), len(r["tokens"])
        if not k:
            continue
        # room for kmax rows from the first served position, whatever k;
        # widths double up to the engine's capacity: few compiles
        width = WIDTH_STEP
        while width < p - 1 + kmax:
            width *= 2
        width = min(width, cap)
        x = np.zeros((width,), np.int32)
        x[:len(seq)] = seq
        best = np.asarray(fwd(params, jnp.asarray(x), p - 1, "f32"))[:k]
        if control:
            low = np.asarray(fwd(params, jnp.asarray(x), p - 1,
                                 precision))[:k]
            chosen = np.argmax(low, axis=-1)
        else:
            # column j of the held slice's logits is id first_vocab + j
            chosen = np.asarray(r["tokens"]) - int(
                ref_cfg.get("share", {}).get("first_vocab", 0))
        g = best.max(axis=-1) - best[np.arange(k), chosen]
        gaps += g.tolist()
        per_request.append([r["idx"], p, k, round(float(g.mean()), 6),
                            round(float(g.max()), 6),
                            round(float(g[:HEAD].mean()), 6)])
    return {"served_logit_gap_mean": (sum(gaps) / len(gaps)
                                      if gaps else 0.0),
            "served_logit_gap_request_max": max(
                (row[3] for row in per_request), default=0.0),
            "_widest": max(gaps, default=0.0), "_tokens": len(gaps),
            "_per_request": per_request, "_requests": len(sample)}


def _compare(env, checked: dict, compared: dict) -> None:
    """The two means always; the widest where the cell's limits name it."""
    for key in ("served_logit_gap_mean", "served_logit_gap_request_max"):
        compared[key] = checked[key]
    limits = env.pick(env.manifest.limits(env.cell["name"]), "limits")
    widest = checked["_widest"]
    if "served_logit_gap" in limits:
        compared["served_logit_gap"] = widest
    env.note(f"read served_logit_gap: widest {widest!r} ("
             + ("compared" if "served_logit_gap" in limits
                else "not compared") + ")")


def run(env) -> dict:
    from benchmark import stats
    t = env.traffic
    srv, url, ref, ref_cfg, spec, dtype, spans = start_server(env, t)
    try:
        plan = serve.make_plan(env, t, url, ref_cfg["vocab_size"])
        if plan["mode"] == "closed":
            plan["clients"] = same_work(plan["clients"],
                                        env.pick(t, "engine")["slots"])
        every = [r for q in plan["clients"] for r in q] \
            if plan["mode"] == "closed" else plan["requests"]
        env.requests_by_idx = {r["idx"]: r for r in every}
        env.break_program(server=srv)       # tests only: a no-op in a run
        setup_s = time.perf_counter() - env.t_process
        out, edges = serve.drive(env, srv, plan)
        peak = env.memory_peak_bytes()
        state_meta = srv.engine.state
    finally:
        srv.stop(drain=False)
        _free(srv)
    del srv
    gc.collect()
    m = serve.measure(plan, out)
    for r in out["results"]:
        if r.get("status") != 200:          # what failed, and how
            env.note(f"request {r['idx']}: status {r.get('status')} sent "
                     f"{r['sent_s']:.2f}s done {r.get('done_s')} "
                     f"{str(r.get('error', ''))[:160]}")
    values, finished = m["values"], m["finished"]
    attempted, failed, ok = m["attempted"], m["failed"], m["ok"]
    ctxv = dict(spans, **m["ctxv"])
    ctxv["http_overhead_ms_p50"] = stats.percentile(
        [1e3 * (r["done_s"] - r["sent_s"]) - r["timings"]["total_ms"]
         for r in finished], 50) if finished else None
    (t_a, a), (t_b, b) = edges["open"], edges["close"]

    def d(key):
        return b[key] - a[key]

    steps = d("decode_steps")
    ctxv["decode_step_ms"] = 1e3 * (t_b - t_a) / max(1, steps)
    ctxv["slot_occupancy_pct"] = 100.0 * d("decode_slot_steps") / max(
        1, steps * a["slots"])
    counts = {"requests": attempted, "finished": len(finished),
              "decode_steps": steps, "prefill_chunks": d("prefill_chunks"),
              "prefill_chunk_tokens": d("prefill_chunk_tokens_total"),
              "moe_rows": d("moe_rows")}
    short = sum(1 for r in finished if len(r["tokens"]) != r["max_new"])
    compared = {"token_count_mismatches": short}
    t0 = time.perf_counter()
    checked = check_outputs(env, ref, ref_cfg, spec, dtype, finished, t)
    _compare(env, checked, compared)
    env.note(f"check: reference {time.perf_counter() - t0:.1f}s over "
             f"{checked['_requests']} requests, {checked['_tokens']} served "
             f"tokens; {len(finished)} finished of {attempted} measured, "
             f"{len(out['results'])} posted")
    env.finished = finished
    e = env.pick(t, "engine")
    return {"attempted": attempted, "failed": failed, "compared": compared,
            "memory_peak_bytes": peak, "setup_s": setup_s,
            "counts": counts, "values": values,
            "record": {"spans": spans, "counts": counts, "per_layer": ctxv,
                       "widest_logit_gap": checked["_widest"],
                       "checked": checked["_per_request"],
                       "checked_columns": ["idx", "prompt", "tokens",
                                           "gap_mean", "gap_max",
                                           "gap_head"],
                       "requests": [
                           [r["idx"], round(r["sent_s"], 4),
                            round(r["done_s"], 4), r["prompt_len"],
                            len(r["tokens"]), r["timings"]["queue_ms"],
                            r["timings"]["prefill_ms"]] for r in ok],
                       "request_columns": ["idx", "sent_s", "done_s",
                                           "prompt", "tokens", "queue_ms",
                                           "prefill_ms"]},
            "ctx": {"values": ctxv, "memory_peak_bytes": peak,
                    "ref_cfg": ref_cfg, "engine": e, "state": state_meta}}


def control(env) -> dict:
    """The control: the reference in fp8 (the precision below the bf16 the
    configuration states) in the program's place, at each position of the
    prompts and served tokens of the run this process just made, else of
    sequences of the mix's sizes drawn from the seed. No server runs."""
    from benchmark import datagen
    t = env.traffic
    ref = env.manifest.reference(env.config)
    ref_cfg = env.pick(env.config, "sizes") if env.rehearse else env.config
    spec = ref.param_spec(ref_cfg)
    dtype = env.pick(env.config, "storage_dtype")
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
    checked = check_outputs(env, ref, ref_cfg, spec, dtype, finished, t,
                            precision="fp8", control=True)
    compared = {"token_count_mismatches": 0}
    _compare(env, checked, compared)
    return compared

"""Traffic kind ``serve_blocks``: the paged server under a seeded request
schedule, for a decoder that generates by diffusion over blocks.

The plan, the load generator and the window's arithmetic are
``kinds/serve.py``'s (``make_plan``, ``drive``, ``measure``) and
``loadgen.py``, unchanged. Three things differ:

- *weights*: a leaf at a time in the configuration's storage dtype
  (``benchmark/weights_by_leaf.py``); the program's artifact takes them as
  an argument, the reference reads the same arrays;
- *the warm request*: two blocks past a prompt that ends inside a block,
  so the prefill and the block step (denoising and commit rows) compile;
- *``correct``*: after the window the server is stopped and its pool
  freed. For a seeded sample of ``check_requests`` finished requests (the
  longest always among them) and, in each, a seeded sample of
  ``check_pairs`` (block, denoising step) pairs plus the last whole
  block's last denoising step, the reference's block step runs on the
  served state: the block's lanes committed at earlier steps (and its
  prompt tokens) visible, the others masked, every earlier block as
  served. Compared: ``served_logit_gap_mean`` (the gap by which a token
  committed at that step lies below the reference's best logit at its
  position, averaged over every checked lane),
  ``served_confidence_gap`` (the widest gap, in
  log-probability, by which a position committed at that step lies below
  the most confident position that stayed masked), and exactly:
  ``token_count_mismatches`` and ``unmask_violations`` (every returned
  token committed once, at a step <= ``denoising_steps``, ``n_s`` or more
  a step while that many were masked, the request's forwards adding up).
  The WIDEST logit gap is read and printed (``read served_logit_gap``,
  the record's ``widest_logit_gap``) but not compared: one expert chosen
  otherwise, which bfloat16 rounding does to a near-tie among 128 router
  probabilities, moves a single lane by about what fp8 moves all of
  them, so a sound run's widest gap comes within 1.7 x of the control's
  (``limits/sdar-serve-backlog.json`` has the readings) while its mean
  stays 9 x under it.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmark.manifest import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
serve = load_module(os.path.join(HERE, "serve.py"))

#: the reference's forward is compiled once a width: sequences are padded
#: to a multiple of this (what follows a block is invisible to it)
WIDTH_STEP = 1024


def _sizes(ref_cfg: dict, t: dict) -> dict:
    a = ref_cfg["assumed"]
    steps = int(t["schedule"]["denoising_steps"])
    lanes = int(a["block_length"])
    return {"lanes": lanes, "mask_id": int(a["mask_id"]), "steps": steps,
            "n_s": -(-lanes // steps),
            "threshold": float(t["schedule"]["threshold"]),
            "first_special": int(a["first_special_id"])}


def start_server(env, t: dict):
    """Weights, export, server, warm request."""
    import jax

    from benchmark import program, weights_by_leaf
    from distributed_tensorflow_example_tpu.serving import export_generator
    from distributed_tensorflow_example_tpu.serving_http import (
        PredictServer)

    cfg = program.train_config(env)
    model, ref, ref_cfg, spec = program.build_model(env, cfg)
    z = _sizes(ref_cfg, t)
    if model.cfg.block_length != z["lanes"] or model.cfg.mask_id != z[
            "mask_id"]:
        raise RuntimeError("the configuration's assumed block length and "
                           "mask id are not the program's")
    model.cfg.denoising_steps = z["steps"]
    model.cfg.confidence_threshold = z["threshold"]
    env.break_program(model=model)          # tests only: a no-op in a run
    params = weights_by_leaf.make_params(spec, env.seed, model.param_dtype)
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
                     platforms=tuple(e["platforms"]))
    export_s = time.perf_counter() - t0
    env.mark("export")
    del params
    gc.collect()
    srv = PredictServer(export_dir, port=0, max_queue=e["max_queue"])
    srv.start()
    env.mark("server up (weights loaded)")
    url = f"http://127.0.0.1:{srv.port}/v1/models/{srv.name}:generate"
    t0 = time.perf_counter()
    rs = np.random.RandomState(0)
    warm_len = e["prompt_len"] - z["lanes"] // 2
    warm = rs.randint(110, z["first_special"], warm_len).tolist()
    ans = serve._post(url, {"inputs": {"input_ids": [warm]},
                            "max_new": 2 * z["lanes"]})
    if ans["timings"][0]["forwards"] < 2 * (z["steps"] + 1):
        raise RuntimeError("the warm request did not ride both kinds of "
                           "block-step row")
    compile_s = time.perf_counter() - t0
    env.mark("warm request (compiles prefill and block step)")
    return srv, url, ref, ref_cfg, spec, model.param_dtype, {
        "export_s": export_s, "serve_compile_s": compile_s}


def _blocks(prompt: list, tokens: list, unmask: list, z: dict) -> list:
    """The request's generated blocks that are known whole: (start, the
    block's final tokens, per lane its unmask step; 0 = a prompt token)."""
    lanes = z["lanes"]
    p = len(prompt)
    seq = list(prompt) + list(tokens)
    steps = [0] * p + list(unmask)
    first = p - p % lanes
    return [(s, seq[s:s + lanes], steps[s:s + lanes])
            for s in range(first, len(seq) - lanes + 1, lanes)]


def unmask_violations(r: dict, prompt: list, z: dict) -> int:
    """How far a finished request's record is from what the schedule
    allows (0 = sound)."""
    unmask = r["timings"].get("unmask_step")
    forwards = r["timings"].get("forwards")
    if unmask is None or forwards is None or len(unmask) != len(r["tokens"]):
        return 1
    bad = sum(1 for u in unmask if not 1 <= u <= z["steps"])
    used = 0
    blocks = _blocks(prompt, r["tokens"], unmask, z)
    for _, _, steps in blocks:
        masked = sum(1 for u in steps if u)
        last = max(steps)
        for s in range(1, last + 1):
            n = sum(1 for u in steps if u == s)
            if n < min(z["n_s"], masked):
                bad += 1
            masked -= n
        used += last + 1                    # its denoising forwards + commit
    # a block cut by max_new is not among them: it cost 2..steps + 1
    whole = (len(prompt) + len(r["tokens"])) % z["lanes"] == 0
    lo, hi = (used, used) if whole else (used + 2, used + z["steps"] + 1)
    return bad + (0 if lo <= forwards <= hi else 1)


def _gaps(logits32: np.ndarray, masked: list, chosen: list,
          chosen_ids: list) -> tuple[list, float]:
    """One (block, step) state. ``logits32`` [B, V]: the reference's;
    ``masked``: lanes masked in the state; ``chosen`` of them were
    committed at this step, as ``chosen_ids``. Per chosen lane its logit
    gap, and the state's confidence gap."""
    best = logits32.max(axis=-1)
    m = logits32.max(axis=-1, keepdims=True)
    logp_best = -np.log(np.exp(logits32 - m).sum(axis=-1))  # max log-prob
    logit_gaps = [float(best[j] - logits32[j, t])
                  for j, t in zip(chosen, chosen_ids)]
    stayed = [j for j in masked if j not in chosen]
    conf_gap = max((float(logp_best[s] - logp_best[c])
                    for s in stayed for c in chosen), default=0.0)
    return logit_gaps, max(conf_gap, 0.0)


def check_outputs(env, ref, ref_cfg, spec, dtype, finished: list, t: dict,
                  precision: str = "f32", control: bool = False) -> dict:
    """The plain reference over sampled (block, step) states of sampled
    requests. ``control``: what the lower ``precision`` would have
    committed at the same states, held to the float32 reference."""
    import jax
    import jax.numpy as jnp

    from benchmark import datagen, weights_by_leaf
    z = _sizes(ref_cfg, t)
    lanes = z["lanes"]
    e = env.pick(t, "engine")
    n = min(int(env.pick(t, "check_requests")), len(finished))
    order = sorted(finished, key=lambda r: -(r["prompt_len"]
                                             + len(r["tokens"])))
    rest = order[1:]
    datagen.rng(env.seed, 5).shuffle(rest)
    sample = [order[0], *rest[:n - 1]]       # the longest is always in it
    rs = datagen.rng(env.seed, 7)
    params = weights_by_leaf.make_params(spec, env.seed, dtype)
    fwd = jax.jit(lambda p, x, start, prec: ref.block_logits_at(
        ref_cfg, p, x, start, lanes, prec), static_argnums=3)
    cap = e["prompt_len"] + e["max_new_tokens"] + lanes
    lane_gaps: list = []
    conf_gap = 0.0
    states = []
    for r in sample:
        prompt = env.requests_by_idx[r["idx"]]["prompt"]
        blocks = _blocks(prompt, r["tokens"], r["timings"]["unmask_step"], z)
        pairs = [(b, s) for b, (_, _, steps) in enumerate(blocks)
                 for s in range(1, max(steps) + 1)]
        if not pairs:
            continue
        last = pairs[-1]                     # the last block's last step
        rs.shuffle(pairs)
        picked = [q for q in pairs if q != last][:int(
            env.pick(t, "check_pairs", 4))] + [last]
        seq = list(prompt) + list(r["tokens"])
        width = min(cap, -(-len(seq) // WIDTH_STEP) * WIDTH_STEP)
        for b, s in picked:
            start, final, steps = blocks[b]
            x = np.zeros((width,), np.int32)
            x[:start] = seq[:start]
            x[start:start + lanes] = [
                tok if u < s else z["mask_id"]
                for tok, u in zip(final, steps)]
            masked = [j for j in range(lanes) if steps[j] >= s]
            best = np.asarray(fwd(params, jnp.asarray(x), start, "f32"))
            if control:
                low = np.asarray(fwd(params, jnp.asarray(x), start,
                                     precision))
                conf = low.max(-1) - np.log(np.exp(
                    low - low.max(-1, keepdims=True)).sum(-1))
                chosen = [j for j in masked
                          if np.exp(conf[j]) > z["threshold"]]
                if len(chosen) < z["n_s"]:
                    chosen = sorted(masked,
                                    key=lambda j: (-conf[j], j))[:z["n_s"]]
                ids = [int(low[j].argmax()) for j in chosen]
            else:
                chosen = [j for j in masked if steps[j] == s]
                ids = [final[j] for j in chosen]
            lg, cg = _gaps(best, masked, chosen, ids)
            lane_gaps += lg
            conf_gap = max(conf_gap, cg)
            states.append([r["idx"], start, s, [round(g, 5) for g in lg],
                           round(cg, 5)])
    return {"served_logit_gap_mean": (sum(lane_gaps) / len(lane_gaps)
                                      if lane_gaps else 0.0),
            "served_confidence_gap": conf_gap,
            "_widest": max(lane_gaps, default=0.0),
            "_states": states, "_requests": len(sample)}


def run(env) -> dict:
    from benchmark import stats
    t = env.traffic
    srv, url, ref, ref_cfg, spec, dtype, spans = start_server(env, t)
    z = _sizes(ref_cfg, t)
    try:
        plan = serve.make_plan(env, t, url, z["first_special"])
        every = [r for q in plan["clients"] for r in q] \
            if plan["mode"] == "closed" else plan["requests"]
        env.requests_by_idx = {r["idx"]: r for r in every}
        env.break_program(server=srv)       # tests only: a no-op in a run
        setup_s = time.perf_counter() - env.t_process
        out, edges = serve.drive(env, srv, plan)
        peak = env.memory_peak_bytes()
    finally:
        srv.stop(drain=False)
    del srv                                  # the pool and the weights go
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

    steps = d("block_steps")
    ctxv["block_step_ms"] = 1e3 * (t_b - t_a) / max(1, steps)
    ctxv["slot_occupancy_pct"] = 100.0 * d("decode_slot_steps") / max(
        1, steps * a["slots"])
    ctxv["forwards_per_block"] = (
        d("denoise_forwards") + d("commit_forwards")) / max(
            1, d("commit_forwards"))
    ctxv["tokens_per_step"] = d("tokens_committed") / max(1, steps)
    counts = {"requests": attempted, "finished": len(finished),
              "block_steps": steps,
              "denoise_forwards": d("denoise_forwards"),
              "commit_forwards": d("commit_forwards"),
              "tokens_committed": d("tokens_committed"),
              "moe_rows": d("moe_rows")}
    short = sum(1 for r in finished if len(r["tokens"]) != r["max_new"])
    compared = {
        "token_count_mismatches": short,
        "unmask_violations": sum(
            unmask_violations(r, env.requests_by_idx[r["idx"]]["prompt"], z)
            for r in finished)}
    t0 = time.perf_counter()
    checked = check_outputs(env, ref, ref_cfg, spec, dtype, finished, t)
    states = checked.pop("_states")
    widest = checked.pop("_widest")
    env.note(f"read served_logit_gap: widest {widest!r} (not compared)")
    env.note(f"check: reference {time.perf_counter() - t0:.1f}s over "
             f"{checked.pop('_requests')} requests, "
             f"{len(states)} (block, step) states; "
             f"{len(finished)} finished of {attempted} measured, "
             f"{len(out['results'])} posted")
    compared.update(checked)
    env.finished = finished
    e = env.pick(t, "engine")
    return {"attempted": attempted, "failed": failed, "compared": compared,
            "memory_peak_bytes": peak, "setup_s": setup_s,
            "counts": counts, "values": values,
            "record": {"spans": spans, "counts": counts, "per_layer": ctxv,
                       "states": states, "widest_logit_gap": widest,
                       "state_columns": ["idx", "block_start", "step",
                                         "lane_logit_gaps",
                                         "confidence_gap"],
                       "requests": [
                           [r["idx"], round(r["sent_s"], 4),
                            round(r["done_s"], 4), r["prompt_len"],
                            len(r["tokens"]), r["timings"]["queue_ms"],
                            r["timings"]["forwards"]] for r in ok],
                       "request_columns": ["idx", "sent_s", "done_s",
                                           "prompt", "tokens", "queue_ms",
                                           "forwards"]},
            "ctx": {"values": ctxv, "memory_peak_bytes": peak,
                    "ref_cfg": ref_cfg, "engine": e}}


def control(env) -> dict:
    """The control: the reference in fp8 (the precision below the bf16 the
    configuration states) in the program's place, at (block, step) states
    of the run this process just made, else of sequences of the mix's
    sizes drawn from the seed with the schedule's own unmask pattern. No
    server runs for it."""
    from benchmark import datagen
    t = env.traffic
    ref = env.manifest.reference(env.config)
    ref_cfg = env.pick(env.config, "sizes") if env.rehearse else env.config
    spec = ref.param_spec(ref_cfg)
    z = _sizes(ref_cfg, t)
    dtype = env.pick(env.config, "storage_dtype")
    finished = getattr(env, "finished", None)
    if not finished:
        n = int(env.pick(t, "check_requests"))
        reqs = datagen.serving_requests(
            env.pick(t, "mix"), z["first_special"],
            datagen.rng(env.seed, 3), 8 * n)
        rs = datagen.rng(env.seed, 6)
        env.requests_by_idx = {r["idx"]: r for r in reqs}
        finished = []
        for r in reqs:
            k = r["max_new"]
            p = len(r["prompt"])
            # a sound record: a block's masked lanes commit n_s at a
            # time in lane order. Token t is the t-th masked lane of the
            # block the prompt ends in, lane (p + t) % B of a later one
            t_ = np.arange(k)
            idx = np.where(t_ < (-p) % z["lanes"], t_,
                           (p + t_) % z["lanes"])
            finished.append({
                "idx": r["idx"], "prompt_len": p,
                "tokens": rs.randint(110, z["first_special"], k).tolist(),
                "timings": {"unmask_step": (1 + idx // z["n_s"]).tolist(),
                            "forwards": 0}})
    out = check_outputs(env, ref, ref_cfg, spec, dtype, finished, t,
                        precision="fp8", control=True)
    env.note(f"read served_logit_gap: widest {out['_widest']!r} "
             "(not compared)")
    return {"served_logit_gap_mean": out["served_logit_gap_mean"],
            "served_confidence_gap": out["served_confidence_gap"],
            "token_count_mismatches": 0, "unmask_violations": 0}

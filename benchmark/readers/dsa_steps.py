"""The executed programs of a SELECTING artifact in a traced window
(``jit_prefill_chunk``, ``jit_decode``), parsed by
``readers/state_steps.py`` (each program beside the span that dispatched
it and the device operations inside it, their whole text). What differs
is what marks an operation as part of a computation: the shapes of the
indexer, of the selected attention and of the window layers, from the
engine's state specs and the configuration. An operation is charged to
the first computation whose pattern its text holds, in the order of
``ORDER``; a loop is one operation, charged by what it carries.

``None`` where the engine kept no index pool (every other cell, and the
parent of PR 35)."""

from __future__ import annotations

import json
import os
import re

from benchmark.readers import state_steps

_KEY = "_dsa_steps"
ORDER = ("index", "attn", "window", "moe")


def steps(ctx: dict):
    st = ctx.get("state") or {}
    if "cache_index" not in st.get("specs", {}):
        return None
    if _KEY not in ctx:
        ctx[_KEY] = state_steps.steps(ctx)
        # look at the attribution by hand: beside the runs' records
        # (BENCHMARK_KEEP_TRACE is state_steps' own, and describes the
        # recurrent kinds' shapes)
        keep = os.environ.get("BENCHMARK_RECORD_DIR")
        if keep and ctx[_KEY]:
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, "dsa_steps.txt"), "w") as f:
                f.write("\n".join(describe(ctx)))
            with open(os.path.join(keep, "dsa_steps_sample.json"),
                      "w") as f:
                json.dump(sample(ctx), f)
    return ctx[_KEY]


def sample(ctx: dict) -> dict:
    """One executed program of each kind as the readers see it (its
    span's arguments, its operations' start, end and text): what
    ``benchmark/tests`` replays."""
    found = ctx[_KEY]
    out = {"modules_s": found["modules_s"]}
    for program in state_steps.PROGRAMS:
        progs = [p for p in found[program] if p["args"]]
        if progs:
            p = max(progs, key=lambda p: p["module"][1] - p["module"][0])
            out[program] = {"args": p["args"], "module": p["module"],
                            "ops": [[a, b, text[:320]] for a, b, text
                                    in _outermost(p["ops"])]}
    return out


def sizes(ctx: dict) -> dict:
    cfg, e = ctx["ref_cfg"], ctx["engine"]
    spec = ctx["state"]["specs"]
    n_full, _, bs, row = spec["cache_latent"]["shape"]
    n_win, slots, ring, wrow = spec["cache_window"]["shape"]
    chunk = int(e["prefill_chunk"])
    total = int(e["prompt_len"]) + int(e["max_new_tokens"])
    return dict(
        slots=slots, bs=bs, chunk=chunk, row=row, wrow=wrow, ring=ring,
        n_full=n_full, n_win=n_win, idim=spec["cache_index"]["shape"][3],
        # the widths of a slot's table and of a prompt's table row
        t_step=-(-total // bs) * bs,
        t_chunk=-(-int(e["prompt_len"]) // chunk) * chunk,
        j=cfg["index_n_heads"], k=cfg["index_topk"],
        window=cfg["sliding_window_size"],
        h=cfg["num_attention_heads"], hw=cfg["swa_num_attention_heads"],
        rank=cfg["kv_lora_rank"], wrank=cfg["swa_kv_lora_rank"],
        qk=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"],
        wqk=cfg["swa_qk_nope_head_dim"] + cfg["swa_qk_rope_head_dim"],
        wv=cfg["swa_v_head_dim"], hidden=cfg["hidden_size"],
        f=cfg["moe_intermediate_size"], picks=cfg["num_experts_per_tok"])


def patterns(ctx: dict, program: str) -> dict:
    z = sizes(ctx)
    s, c, h, hw = z["slots"], z["chunk"], z["h"], z["hw"]
    f = z["f"]
    widths = f"{z['hidden']}|{f}|{2 * f}"
    if program == "decode":
        t, k, pairs = z["t_step"], z["k"], s * z["picks"]
        return {
            # the gathered index keys, the scores, their sort
            "index": rf"\[{s},{t},{z['idim']}\]|\[{s},{t // z['bs']},"
                     rf"{z['bs']},{z['idim']}\]|\[{s},{z['j']},{t}\]"
                     rf"|(?:f32|s32|u32)\[{s},{t}\]",
            # the gathered latent rows, the scores over them
            "attn": rf"\[{s},{k},{z['row']}\]|\[{s * k},{z['row']}\]"
                    rf"|\[{s},{h},{k}\]|\[{s},{k},{z['rank']}\]"
                    rf"|\[{s},{k}\]|\[{s * k}\]",
            "window": rf"\[{s},{z['ring']},(?:{z['wrow']}|{z['wrank']})\]"
                      rf"|\[{s},{hw},{z['ring']}\]",
            "moe": rf"ragged-dot|\[{pairs},(?:{widths})\]|s32\[{pairs}\]",
        }
    t, pairs = z["t_chunk"], c * z["picks"]
    tile = min(512, c)
    prev = z["window"] - 1
    return {
        # a tile's scores by head, the score matrix, the bisection over
        # the whole width or the half, quarter or eighth the context
        # reaches
        "index": rf"\[{c * z['j']},{tile}\]|\[{c},{z['j']},{tile}\]"
                 rf"|(?:f32|u32|s32)\[{c},(?:{t}|{t // 2}|{t // 4}|"
                 rf"{t // 8})\]|\[{c},{t // z['bs']},{z['bs']}\]",
        # the kernel by its name, its operands head-major (queries,
        # W_kvb, the mask as bytes); or the dense tiles under the mask:
        # scores, K and V, accumulator
        "attn": rf"dsa_selected_attn|\[{h},{c},(?:{z['qk']}|{z['v']})\]"
                rf"|\[{h},{z['rank']},\d+\]|s8\[{c},{t}\]"
                rf"|\[{h},{c},{tile}\]|\[{tile},{h},\d+\]"
                rf"|f32\[{h},{c}\]",
        "window": rf"\[{hw},{prev},{2 * prev}\]|\[{prev + c},{hw},\d+\]"
                  rf"|\[{prev + c},(?:{z['wrow']}|{z['wrank']})\]"
                  rf"|\[{hw},{c},{prev + c}\]",
        "moe": rf"ragged-dot|\[{pairs},(?:{widths})\]|s32\[{pairs}\]",
    }


def split(ctx: dict, program: str, ops: list) -> dict:
    """Device seconds of one program's outermost operations by
    computation (``ORDER``; ``other``: claimed by none)."""
    out = dict.fromkeys([*ORDER, "other"], 0.0)
    for kind, seconds, _ in _charged(ctx, program, ops):
        out[kind] += seconds
    return out


def _outermost(ops: list):
    """The operations no earlier one encloses (a loop's body nests inside
    the loop's own event)."""
    end = 0.0
    for a, b, text in ops:
        if a >= end:
            end = b
            yield a, b, text


def _charged(ctx: dict, program: str, ops: list):
    """``(computation, seconds, text)`` of each outermost operation."""
    rx = {k: re.compile(v) for k, v in patterns(ctx, program).items()}
    for a, b, text in _outermost(ops):
        yield (next((k for k in ORDER if rx[k].search(text)), "other"),
               b - a, text)


def totals(ctx: dict, program: str):
    """Per program of ``program``: ``(span arguments, seconds by
    computation)``."""
    found = steps(ctx)
    if not found or not found.get(program):
        return None
    key = f"{_KEY}_{program}"
    if key not in ctx:
        ctx[key] = [(p["args"], split(ctx, program, p["ops"]))
                    for p in found[program]]
    return ctx[key]


def describe(ctx: dict) -> list[str]:
    found, out = ctx[_KEY], []
    for program in state_steps.PROGRAMS:
        progs = found[program]
        total = sum(m1 - m0 for m0, m1 in (p["module"] for p in progs))
        out.append(f"{program}: {len(progs)} programs, {total:.6f} s")
        by: dict = {}
        for p in progs:
            for kind, seconds, text in _charged(ctx, program, p["ops"]):
                row = by.setdefault((kind, text[:200]), [0.0, 0])
                row[0] += seconds
                row[1] += 1
        for kind in (*ORDER, "other"):
            t = sum(v[0] for (k, _), v in by.items() if k == kind)
            out.append(f"  {kind}: {t:.6f} s")
        for (kind, text), (t, n) in sorted(by.items(),
                                           key=lambda kv: -kv[1][0])[:60]:
            out.append(f"  {t:.6f} s x{n} [{kind}]  {text}")
    return out

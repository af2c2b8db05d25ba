"""The executed programs of a GROUPED-QUERY state artifact in a traced
window (``jit_prefill_chunk``, ``jit_decode``), parsed by
``readers/state_steps.py`` (each program beside the span that dispatched
it and the device operations inside it, their whole text). What marks an
operation as part of a computation: the kernels by their names
(``paged_gqa_attn``, ``gqa_chunk_attn``) and XLA's operations by the
shapes they touch, from the engine's state specs and the configuration
(the two kinds of layer bring different head counts, so a chunk's
``[1024, 48, ..]`` is a full layer's and its ``[1024, 72, ..]`` a window
layer's). An operation is charged to the first computation whose pattern
its text holds, in the order of ``ORDER``; a loop or a conditional is one
operation, charged by what it carries.

``None`` where the engine kept no K/V rings (every other cell, and the
parent of PR 41)."""

from __future__ import annotations

import json
import os
import re

from benchmark.readers import state_steps
from benchmark.readers.dsa_steps import _outermost

_KEY = "_gqa_steps"
ORDER = ("attn", "window", "moe")


def steps(ctx: dict):
    st = ctx.get("state") or {}
    if "cache_window_k" not in st.get("specs", {}):
        return None
    if _KEY not in ctx:
        ctx[_KEY] = state_steps.steps(ctx)
        keep = os.environ.get("BENCHMARK_RECORD_DIR")
        if keep and ctx[_KEY]:      # look at the attribution by hand
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, "gqa_steps.txt"), "w") as f:
                f.write("\n".join(describe(ctx)))
            with open(os.path.join(keep, "gqa_steps_sample.json"),
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
    n_full, _, bs, row = spec["cache_k"]["shape"]
    n_win, slots, ring, _ = spec["cache_window_k"]["shape"]
    layers = int(ctx["state"]["layers"])
    heads = list(cfg["num_attention_heads_per_layer"][:layers])
    kinds = list(cfg["layer_types"][:layers])
    st = ctx["state"]
    return dict(
        slots=slots, bs=bs, chunk=int(e["prefill_chunk"]), row=row,
        ring=ring, n_full=n_full, n_win=n_win, d=cfg["head_dim"],
        kvh=cfg["num_key_value_heads"],
        h=heads[kinds.index("full_attention")],
        hw=heads[kinds.index("sliding_attention")],
        window=cfg["sliding_window"], hidden=cfg["hidden_size"],
        f=cfg["moe_intermediate_size"], picks=cfg["num_experts_per_tok"],
        sparse=st["ffns"].count("moe"), held=st["experts_held"],
        experts=st["experts"],
        # the rows a chunk's expert layers run over (ops/moe.pair_bound)
        bound=(st.get("moe_rows", {}).get("prefill_chunk", {})
               .get("bound")))


def patterns(ctx: dict, program: str) -> dict:
    z = sizes(ctx)
    s, c, h, hw = z["slots"], z["chunk"], z["h"], z["hw"]
    f, d = z["f"], z["d"]
    widths = f"{z['hidden']}|{f}|{2 * f}"
    if program == "decode":
        pairs = s * z["picks"]
        return {
            # the kernel by its name; its query tile and its context
            "attn": rf"paged_gqa_attn|\[{s},{h},(?:{z['row']}|{d})\]"
                    rf"|\[{s},{h},{z['kvh']},{d}\]"
                    rf"|\[{s},{-(-h // 16) * 16},{z['row']}\]",
            # the rings as they lie, the scores over them, the tile
            "window": rf"\[{s},{z['ring']},{z['row']}\]"
                      rf"|\[{s},{hw},(?:{z['ring']}|{z['row']}|{d})\]"
                      rf"|\[{s},{hw},{z['kvh']},{d}\]",
            "moe": rf"ragged-dot|\[{pairs},(?:{widths})\]|s32\[{pairs}\]",
        }
    pairs = c * z["picks"]
    rows = "|".join(str(r) for r in {pairs, z["bound"] or pairs})
    return {
        # the kernel by its name (both kinds of layer run it: told apart
        # by the head count of its operands), q head-major and back
        "attn": rf"gqa_chunk_attn[^\n]*\[{h},{c},{d}\]"
                rf"|\[{h},{c},{d}\]|\[{c},{h},(?:{d}|{d // 2}|{d // 4})\]",
        "window": rf"gqa_chunk_attn[^\n]*\[{hw},{c},{d}\]"
                  rf"|\[{hw},{c},{d}\]|\[{c},{hw},(?:{d}|{d // 2})\]"
                  rf"|\[{z['ring'] + c},{z['row']}\]"
                  rf"|\[\d+,{z['bs']},{z['row']}\]",
        "moe": rf"ragged-dot|\[(?:{rows}),(?:{widths})\]"
               rf"|s32\[(?:{rows})\]",
    }


def _charged(ctx: dict, program: str, ops: list):
    """``(computation, seconds, text)`` of each outermost operation."""
    rx = {k: re.compile(v) for k, v in patterns(ctx, program).items()}
    for a, b, text in _outermost(ops):
        yield (next((k for k in ORDER if rx[k].search(text)), "other"),
               b - a, text)


def split(ctx: dict, program: str, ops: list) -> dict:
    """Device seconds of one program's outermost operations by
    computation (``ORDER``; ``other``: claimed by none)."""
    out = dict.fromkeys([*ORDER, "other"], 0.0)
    for kind, seconds, _ in _charged(ctx, program, ops):
        out[kind] += seconds
    return out


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

"""The executed programs of a DENSE-LATENT state artifact in a traced
window (``jit_prefill_chunk``, ``jit_decode``), parsed by
``readers/state_steps.py`` (each program beside the span that dispatched
it and the device operations inside it, their whole text). The two
attention forms are read by their kernels' NAMES (``mla_chunk_attn``: the
chunk's expanded form; ``paged_latent_attn``: the step's absorbed form),
the expert layers by ``ragged-dot``, the ``conditional`` of a bounded
layer and the rows they run over (``export.json`` ``moe_rows``); an
operation is charged to the first of ``ORDER`` whose pattern its text
holds; a loop or a conditional is one operation.

``None`` where the engine's state is not a latent pool alone under
``mla_dense`` mixers (every other cell, and the parent of PR 44)."""

from __future__ import annotations

import os
import re

from benchmark.readers import state_steps
from benchmark.readers.dsa_steps import _outermost

_KEY = "_mla_dense_steps"
ORDER = ("attn", "moe")
KERNELS = {"prefill_chunk": "mla_chunk_attn", "decode": "paged_latent_attn"}


def steps(ctx: dict):
    st = ctx.get("state") or {}
    if set(st.get("mixers") or ["?"]) != {"mla_dense"}:
        return None
    if _KEY not in ctx:
        ctx[_KEY] = state_steps.steps(ctx)
        keep = os.environ.get("BENCHMARK_RECORD_DIR")
        if keep and ctx[_KEY]:      # look at the attribution by hand
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, "mla_steps.txt"), "w") as f:
                f.write("\n".join(describe(ctx)))
    return ctx[_KEY]


def sizes(ctx: dict) -> dict:
    cfg, st = ctx["ref_cfg"], ctx["state"]
    layers, _, bs, row = st["specs"]["cache_latent"]["shape"]
    return dict(
        layers=layers, bs=bs, row=row, heads=cfg["num_attention_heads"],
        rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        pe=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        hidden=cfg["hidden_size"], f=cfg["moe_intermediate_size"],
        moe_rows=st.get("moe_rows", {}))


def patterns(ctx: dict, program: str) -> dict:
    z = sizes(ctx)
    rows = z["moe_rows"].get(program, {})
    # the program's own rows: a bound as wide as they are (a chunk of 1,024
    # under a bound of 1,024) marks nothing, every activation has it
    own = int(ctx["engine"]["prefill_chunk"]) if program == "prefill_chunk" \
        else int(ctx["engine"]["slots"])
    counts = "|".join(str(r) for r in sorted(
        {rows.get("pairs"), rows.get("bound")} - {None, own})) or "0"
    picks = (rows.get("pairs") or 0) // own
    widths = f"{z['hidden']}|{z['f']}|{2 * z['f']}"
    return {"attn": KERNELS[program],
            "moe": rf"ragged-dot| conditional\(|\[(?:{counts}),(?:{widths})\]"
                   rf"|s32\[(?:{counts})\]|\[{own},{picks},{z['hidden']}\]"}


def _charged(ctx: dict, program: str, ops: list):
    rx = {k: re.compile(v) for k, v in patterns(ctx, program).items()}
    for a, b, text in _outermost(ops):
        yield (next((k for k in ORDER if rx[k].search(text)), "other"),
               b - a, text)


def totals(ctx: dict, program: str):
    """Per executed program of ``program``: ``(span arguments, device
    seconds by computation)`` (``ORDER`` and ``other``)."""
    found = steps(ctx)
    if not found or not found.get(program):
        return None
    key = f"{_KEY}_{program}"
    if key not in ctx:
        out = []
        for p in found[program]:
            by = dict.fromkeys([*ORDER, "other"], 0.0)
            for kind, seconds, _ in _charged(ctx, program, p["ops"]):
                by[kind] += seconds
            out.append((p["args"], by))
        ctx[key] = out
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

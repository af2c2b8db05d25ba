"""The executed programs of a per-request-state artifact in a traced
window, ``jit_decode`` and ``jit_prefill_chunk``, each beside the span
that dispatched it (``decode_step`` with ``kv_bytes``, ``state_bytes``,
``expert_rows``; ``prefill_chunk`` with ``tokens``, ``start``) and the
device operations inside it as the capture names them: the instruction's
whole text, SHAPES included. No named scope reaches a capture and XLA
names a fusion after its operations, so what an operation belongs to is
read from the shapes it touches: only KDA's state update touches
``f32[slots, heads, d, d]``, only the chunked scan ``[sub-chunks, heads,
sub-chunk, ..]`` (whatever power of two the sub-chunk is), only the
expert layer's gather, grouped matmuls and combine ``[rows x picks,
hidden or expert width]``. A kernel is read by its own name
(``paged_latent_attn``), a grouped matmul by its opcode's
(``ragged-dot``).

``None`` where the capture holds no such program (every other cell, and
the parent of PR 32)."""

from __future__ import annotations

import os
import re

from benchmark.readers import xplane_join

_KEY = "_state_steps"
PROGRAMS = {"decode": ("jit_decode", "decode_step"),
            "prefill_chunk": ("jit_prefill_chunk", "prefill_chunk")}


def steps(ctx: dict):
    """Parsed once a run and cached on ``ctx``; ``None`` for a run whose
    engine kept no per-request state (another artifact's ``jit_decode``
    is not this one's)."""
    if not ctx.get("state"):
        return None
    if _KEY not in ctx:
        path = ctx.get("xplane_path") or xplane_join.find_capture(
            ctx["trace"])
        ctx[_KEY] = _parse_with_text(path) if path is not None else None
        keep = os.environ.get("BENCHMARK_KEEP_TRACE")
        if keep and ctx[_KEY]:      # look at the attribution by hand
            with open(os.path.join(keep, "state_steps.txt"), "w") as f:
                f.write("\n".join(describe(ctx)))
    return ctx[_KEY]


def describe(ctx: dict) -> list[str]:
    """Per program: device seconds by pattern, and the operations no
    pattern claims, largest first."""
    found, out = ctx[_KEY], []
    for key in PROGRAMS:
        progs, pats = found[key], patterns(ctx, key)
        total = sum(m1 - m0 for m0, m1 in (p["module"] for p in progs))
        out.append(f"{key}: {len(progs)} programs, {total:.6f} s")
        for name, pattern in pats.items():
            t = sum(seconds(p["ops"], pattern) for p in progs)
            out.append(f"  {name}: {t:.6f} s")
        rest: dict = {}
        rx = re.compile("|".join(pats.values()))
        for p in progs:
            end = 0.0
            for a, b, text in p["ops"]:
                if a >= end and not rx.search(text):
                    rest[text[:160]] = rest.get(text[:160], 0.0) + b - a
                    end = b
        for text, t in sorted(rest.items(), key=lambda kv: -kv[1])[:25]:
            out.append(f"  unclaimed {t:.6f} s  {text}")
    return out


def _parse_with_text(path: str):
    """As ``readers/block_steps.parse``, for two programs and keeping
    each operation's text."""
    import bisect

    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    spans = {span: [] for _, span in PROGRAMS.values()}
    for plane in planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(
                            (e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9,
                             dict(e.stats)))
    for v in spans.values():
        v.sort(key=lambda s: s[0])
    starts = {name: [s[0] for s in v] for name, v in spans.items()}
    out = {key: [] for key in PROGRAMS}
    busy = 0.0
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        ops = sorted((e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9, e.name)
                     for e in lines["XLA Ops"].events)
        op_starts = [o[0] for o in ops]
        for m in lines["XLA Modules"].events:
            m0 = m.start_ns * 1e-9
            m1 = m0 + m.duration_ns * 1e-9
            busy += m1 - m0
            for key, (module, span) in PROGRAMS.items():
                if not m.name.startswith(module + "("):
                    continue
                sp = spans[span]
                i = bisect.bisect_right(starts[span], m0 + 2e-3) - 1
                args = sp[i][2] if i >= 0 and m0 < sp[i][1] + 2e-3 else {}
                out[key].append({
                    "args": args, "module": (m0, m1),
                    "ops": ops[bisect.bisect_left(op_starts, m0):
                               bisect.bisect_left(op_starts, m1)]})
        break                               # the first chip that ran any
    if not any(out.values()):
        return None
    out["modules_s"] = busy
    return out


def seconds(ops: list, pattern: str) -> float:
    """Device seconds of the operations whose text matches ``pattern``,
    outermost only (a loop's body nests inside the loop's own event)."""
    total, end = 0.0, 0.0
    rx = re.compile(pattern)
    for a, b, text in ops:
        if a >= end and rx.search(text):
            total += b - a
            end = b
    return total


def patterns(ctx: dict, program: str = "decode") -> dict:
    """What marks an operation of ``program`` as part of each computation,
    from the shapes the engine's state specs and the configuration
    give."""
    spec = ctx["state"]["specs"]["cache_state"]["shape"]
    _, slots, heads, d, _ = spec
    chunk = int(ctx["engine"]["prefill_chunk"])
    # the scan's sub-chunk is the program's to choose: any power of two
    subs = [s for s in (8, 16, 32, 64, 128, 256) if chunk % s == 0]
    scan = "|".join(rf"\[{chunk // s},{heads},(?:1,)?{s},"
                    rf"|\[{heads},{s},(?:{d}|{s}|{2 * d})\]" for s in subs)
    cfg = ctx["ref_cfg"]
    # a row's picks, gathered side by side: [pairs, hidden] into the
    # grouped matmuls, [pairs, expert width] between them, back again
    pairs = ((slots if program == "decode" else chunk)
             * int(cfg["num_experts_per_token"]))
    f = int(cfg["moe_intermediate_size"])
    widths = f"{int(cfg['hidden_size'])}|{f}|{2 * f}"
    return {
        "kda_step": rf"\[(?:\d+,)?{slots},{heads},{d},{d}\]",
        "kda_chunk": rf"{scan}|f32\[{heads},{d},{d}\]|triangular-solve",
        "mla_attn": r"paged_latent_attn",
        "ragged": r"ragged-dot",
        "moe": rf"ragged-dot|sort|\[{pairs},(?:{widths})\]",
    }


def state_share(ctx: dict) -> float:
    """Of the recurrent bytes a slot holds (the ``decode_step`` span's
    ``state_bytes`` counts them all), the share that is the state array
    itself: the convolutions' tails are updated by other operations."""
    import numpy as np
    per_slot = {k: int(np.prod(v["shape"])) * np.dtype(v["dtype"]).itemsize
                for k, v in ctx["state"]["specs"].items()
                if v["per"] == "slot"}
    return per_slot["cache_state"] / sum(per_slot.values())

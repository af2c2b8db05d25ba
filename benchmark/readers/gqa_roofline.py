"""Share of its roofline one computation of a grouped-query state
artifact reached in the traced programs: ``what`` = ``attn`` (a decode
step's full-layer attention: the span's ``kv_bytes`` over the
bandwidth), ``window`` (a decode step's ring attention: ``window_bytes``)
or ``chunk`` (a chunk program's two attentions together: their FLOPs over
the peak). The least time is the larger of the required FLOPs over the
peak and the required bytes over the bandwidth
(``benchmark/flops_gqa.py``, from the spans' ``context_rows``,
``kv_bytes``, ``window_bytes``, ``slots`` and ``tokens``), against the
device time of the operations ``readers/gqa_steps.py`` charges to it.
``None`` without such programs."""

from benchmark import flops, flops_gqa
from benchmark.readers import gqa_steps


def _least(z: dict, what: str, a: dict):
    """(FLOPs, bytes, which computations' time) one program requires."""
    ring_row = 2 * z["row"] * 2             # a token's K and V row, bytes
    if what == "attn":
        rows = float(a["slots"])
        return (flops_gqa.attn_flops(float(a["context_rows"]), z["h"],
                                     z["d"]),
                flops_gqa.decode_attn_bytes(float(a["kv_bytes"]), rows,
                                            z["n_full"], z["h"], z["d"]),
                ("attn",))
    if what == "window":
        rows = float(a["slots"])
        pairs = float(a["window_bytes"]) / ring_row
        return (flops_gqa.attn_flops(pairs, z["hw"], z["d"]),
                flops_gqa.window_attn_bytes(float(a["window_bytes"]), rows,
                                            z["n_win"], z["hw"], z["d"]),
                ("window",))
    tokens = float(a["tokens"])
    pairs_w = float(a["window_bytes"]) / ring_row
    seen = (tokens + z["window"] - 1) * z["n_win"] * ring_row
    return (flops_gqa.attn_flops(float(a["context_rows"]), z["h"], z["d"])
            + flops_gqa.attn_flops(pairs_w, z["hw"], z["d"]),
            flops_gqa.chunk_attn_bytes(float(a["kv_bytes"]), tokens,
                                       z["n_full"], z["h"], z["d"])
            + flops_gqa.chunk_attn_bytes(
                min(float(a["window_bytes"]), seen), tokens, z["n_win"],
                z["hw"], z["d"]),
            ("attn", "window"))


def read(ctx: dict, what: str):
    progs = gqa_steps.totals(
        ctx, "prefill_chunk" if what == "chunk" else "decode")
    if not progs:
        return None
    z = gqa_steps.sizes(ctx)
    least = seconds = 0.0
    for args, by in progs:
        if "context_rows" not in args or not float(args.get(
                "slots", args.get("tokens", 0))):
            continue
        ops, moved, kinds = _least(z, what, args)
        took = sum(by[k] for k in kinds)
        if not took:
            continue
        pct, _ = flops.roofline_pct(ops, moved, 1.0,
                                    ctx["peak"]["bf16_flops"],
                                    ctx["peak"]["hbm_bytes_per_s"])
        least += pct / 100.0
        seconds += took
    return 100.0 * least / seconds if seconds else None

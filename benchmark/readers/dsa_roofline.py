"""Share of its roofline one computation of a selecting artifact reached
in the traced chunk programs: ``what`` = ``index`` (index scoring and
selection), ``attn`` (attention over the selected rows) or ``window``
(the window layers' attention). The least time is the larger of the
required FLOPs over the peak and the required bytes over the bandwidth
(``benchmark/flops_dsa.py``, from the ``prefill_chunk`` span's
``context_rows``, ``selected_rows``, ``index_bytes``, ``kv_bytes``,
``window_bytes`` and ``tokens``), against the device time of the
operations ``readers/dsa_steps.py`` charges to it. ``None`` without such
programs."""

from benchmark import flops, flops_dsa
from benchmark.readers import dsa_steps


def _least(z: dict, what: str, a: dict):
    """(FLOPs, bytes) one chunk program's ``what`` requires."""
    tokens = float(a["tokens"])
    if what == "index":
        return (flops_dsa.index_flops(float(a["context_rows"]), z["j"],
                                      z["idim"]),
                flops_dsa.index_bytes(float(a["index_bytes"]), tokens,
                                      z["n_full"], z["j"], z["idim"]))
    if what == "attn":
        # the rows of a chunk share one context: its keys, as latent rows
        keys = float(a["index_bytes"]) / (2 * z["idim"])
        return (flops_dsa.selected_attn_flops(float(a["selected_rows"]),
                                              z["h"], z["qk"], z["v"]),
                flops_dsa.selected_attn_bytes(float(a["kv_bytes"]),
                                              keys * z["row"] * 2))
    row_bytes = z["wrow"] * 2
    seen = (tokens + z["window"] - 1) * z["n_win"] * row_bytes
    return (flops_dsa.window_attn_flops(
                float(a["window_bytes"]) / row_bytes, z["hw"], z["wqk"],
                z["wv"]),
            min(float(a["window_bytes"]), seen))


def read(ctx: dict, what: str):
    progs = dsa_steps.totals(ctx, "prefill_chunk")
    if not progs:
        return None
    z = dsa_steps.sizes(ctx)
    least = seconds = 0.0
    for args, by in progs:
        if not by[what] or "selected_rows" not in args:
            continue
        ops, moved = _least(z, what, args)
        pct, _ = flops.roofline_pct(ops, moved, 1.0,
                                    ctx["peak"]["bf16_flops"],
                                    ctx["peak"]["hbm_bytes_per_s"])
        least += pct / 100.0
        seconds += by[what]
    return 100.0 * least / seconds if seconds else None

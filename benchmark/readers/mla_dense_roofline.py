"""Share of its roofline one form of a dense-latent artifact's attention
reached in the traced programs: ``what`` = ``step`` (the absorbed
attention of a decode step, the kernel ``paged_latent_attn`` by name) or
``chunk`` (the expanded attention of a chunk program, the kernel
``mla_chunk_attn`` by name). The least time is the larger of the required
FLOPs over the peak and the required bytes over the bandwidth
(``benchmark/flops_mla_dense.py``: the cheaper of the two forms, from the
spans' ``context_rows``, ``kv_bytes``, ``slots``, ``tokens`` and
``start`` alone), against the kernel's device time. ``None`` without such
programs or the kernel."""

from benchmark import flops, flops_mla_dense
from benchmark.readers import mla_dense_steps


def read(ctx: dict, what: str):
    program = "decode" if what == "step" else "prefill_chunk"
    progs = mla_dense_steps.totals(ctx, program)
    if not progs:
        return None
    z = mla_dense_steps.sizes(ctx)
    dims = (z["heads"], z["rank"], z["nope"], z["pe"], z["v"])
    least = seconds = 0.0
    for a, by in progs:
        rows = float(a.get("slots" if what == "step" else "tokens", 0))
        if "context_rows" not in a or not rows or not by["attn"]:
            continue
        pairs = float(a["context_rows"])        # summed over layers
        queries = rows * z["layers"]
        # a step's rows each bring a context; a chunk's share one
        keys = pairs if what == "step" else (
            float(a["start"]) + rows) * z["layers"]
        pct, _ = flops.roofline_pct(
            flops_mla_dense.required_flops(pairs, keys, queries, *dims),
            flops_mla_dense.required_bytes(float(a["kv_bytes"]), queries,
                                           *dims[:1], *dims[2:]),
            1.0, ctx["peak"]["bf16_flops"], ctx["peak"]["hbm_bytes_per_s"])
        least += pct / 100.0
        seconds += by["attn"]
    return 100.0 * least / seconds if seconds else None

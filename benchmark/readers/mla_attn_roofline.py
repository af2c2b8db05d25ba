"""Share of its roofline the absorbed latent attention reached in the
traced decode steps: the latent rows the live contexts held (``kv_bytes``
of each ``decode_step`` span: rows as stored, every MLA layer) over the
peak bandwidth, against the device time of the kernel by name
(``paged_latent_attn``). ``None`` without such steps or the kernel."""

from benchmark.readers import state_steps


def read(ctx: dict):
    found = state_steps.steps(ctx)
    if not found or not found["decode"]:
        return None
    pattern = state_steps.patterns(ctx)["mla_attn"]
    moved = seconds = 0.0
    for p in found["decode"]:
        t = state_steps.seconds(p["ops"], pattern)
        if t and "kv_bytes" in p["args"]:
            moved += float(p["args"]["kv_bytes"])
            seconds += t
    if not seconds or not moved:
        return None
    return 100.0 * (moved / ctx["peak"]["hbm_bytes_per_s"]) / seconds

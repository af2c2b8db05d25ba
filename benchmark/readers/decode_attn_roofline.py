"""Share of its roofline the paged decode-attention kernel reached: the
least time the chip could take to read the K and V the traced decode
steps' live rows held (``kv_bytes``, an argument of each ``decode_step``
span: sum of the live slots' positions times the pool's bytes per
token; memory-bound, the kernel's FLOPs are a few per byte) over the
device time of the ``custom-call`` operations that ran inside those
steps (``readers/xplane_join.py``). Only steps that hold exactly one
device ``while`` count: there the host's and the device's clocks agree.
``None`` without the spans, the argument or a kernel."""

from benchmark.readers import xplane_join


def read(ctx: dict, name: str = "decode_step"):
    found = xplane_join.join(ctx)
    if found is None:
        return None
    chip = found["chips"][0]
    lo, hi = chip["window"]
    spans = [s for s in found["spans"].get(name, ())
             if s[0] >= lo and s[1] <= hi and "kv_bytes" in s[2]]
    whiles = xplane_join.ops_inside(chip, spans, "while")
    calls = xplane_join.ops_inside(chip, spans, "custom-call")
    kv_bytes = seconds = 0.0
    for span, w, ops in zip(spans, whiles, calls):
        if len(w) == 1 and ops:
            kv_bytes += float(span[2]["kv_bytes"])
            seconds += sum(b - a for a, b in ops)
    if not seconds or not kv_bytes:
        return None
    return 100.0 * (kv_bytes / ctx["peak"]["hbm_bytes_per_s"]) / seconds

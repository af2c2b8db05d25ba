"""``flash_roofline`` for a cell on several chips: the share of its
roofline the flash-attention kernels reached on ONE chip. The reduced
trace's operation seconds are the mean over the chips, its program
executions the sum over them (``readers/op_ms_per_chip_call.py``), and a
chip holds ``batch_size / chips`` rows of the global batch: the required
operations and bytes (``benchmark/flops.py``) are counted for those rows,
against the kernels' device time per step program on one chip."""

from benchmark import flops, trace_reduce


def read(ctx: dict, per_module: str, pattern: str = "", opcode: str = ""):
    trace = ctx["trace"]
    if ctx.get("family") != "gpt" or not trace.get("chips"):
        return None
    seconds = trace_reduce.op_seconds(trace, opcode, pattern)
    steps = trace_reduce.call_count(trace, per_module) / trace["chips"]
    if not seconds or not steps:
        return None
    cfg, data = ctx["ref_cfg"], ctx["data"]
    shape = dict(batch=data["batch_size"] / trace["chips"],
                 heads=cfg["n_head"], seq_len=data["seq_len"],
                 head_dim=cfg["n_embd"] // cfg["n_head"],
                 layers=cfg["n_layer"])
    pct, _bound = flops.roofline_pct(
        flops.flash_train_flops(**shape), flops.flash_train_bytes(**shape),
        seconds / steps, ctx["peak"]["bf16_flops"],
        ctx["peak"]["hbm_bytes_per_s"])
    return pct

"""Share of its roofline the flash-attention kernels reached in training:
the least time the chip could take for the REQUIRED operations and bytes
(benchmark/flops.py: causal 6*S^2*D FLOPs per head and sequence, the
backward's recomputed QK^T not counted) over the kernels' device time per
step from the trace."""

from benchmark import flops, trace_reduce


def read(ctx: dict, per_module: str, pattern: str = "", opcode: str = ""):
    if ctx.get("family") != "gpt":
        return None
    seconds = trace_reduce.op_seconds(ctx["trace"], opcode, pattern)
    steps = trace_reduce.call_count(ctx["trace"], per_module)
    if not seconds or not steps:
        return None
    cfg, data = ctx["ref_cfg"], ctx["data"]
    shape = dict(batch=data["batch_size"], heads=cfg["n_head"],
                 seq_len=data["seq_len"],
                 head_dim=cfg["n_embd"] // cfg["n_head"],
                 layers=cfg["n_layer"])
    pct, _bound = flops.roofline_pct(
        flops.flash_train_flops(**shape), flops.flash_train_bytes(**shape),
        seconds / steps, ctx["peak"]["bf16_flops"],
        ctx["peak"]["hbm_bytes_per_s"])
    return pct

"""``op_ms_per_call`` for a cell on several chips: the trace's operation
seconds are the mean over the chips that ran anything, its program
executions the sum over them, so a program that runs once a step on each
of four chips is counted four times. Device milliseconds of the
operations (``opcode`` or ``pattern``) on one chip per execution of the
program (``per_module``) on one chip."""

from benchmark import trace_reduce


def read(ctx: dict, opcode: str = "", pattern: str = "",
         per_module: str = ""):
    trace = ctx["trace"]
    seconds = trace_reduce.op_seconds(trace, opcode, pattern)
    calls = trace_reduce.call_count(trace, per_module)
    if not seconds or not calls or not trace.get("chips"):
        return None
    return 1e3 * seconds / (calls / trace["chips"])

"""Device milliseconds of some operations per call of others, from the
trace: a kernel's time per step.

The operations timed are those whose HLO ``opcode`` is given (or whose
name matches ``pattern``), at any depth of the trace. The calls counted
are the executed programs whose name matches ``per_module``, or, where
programs cannot be told apart by name (the served prefill and decode
programs are both ``jit_fn``), the operations of opcode ``per_opcode``
(the decode program holds exactly one ``while``, the scan over layers;
the prefill program holds none)."""

from benchmark import trace_reduce


def read(ctx: dict, opcode: str = "", pattern: str = "",
         per_module: str = "", per_opcode: str = ""):
    seconds = trace_reduce.op_seconds(ctx["trace"], opcode, pattern)
    calls = trace_reduce.call_count(ctx["trace"], per_module, per_opcode)
    if not seconds or not calls:
        return None
    return 1e3 * seconds / calls

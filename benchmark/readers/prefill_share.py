"""The chunk programs' share of the traced window's device time: seconds
inside ``jit_prefill_chunk`` over seconds inside any executed program.
What is left is the decode steps' (and the slot zeroing's). ``None``
without chunk programs in the capture."""

from benchmark.readers import state_steps


def read(ctx: dict):
    found = state_steps.steps(ctx)
    if not found or not found["prefill_chunk"] or not found["modules_s"]:
        return None
    chunk = sum(m1 - m0 for m0, m1 in (
        p["module"] for p in found["prefill_chunk"]))
    return 100.0 * chunk / found["modules_s"]

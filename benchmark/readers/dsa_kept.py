"""Of the rows the traced dispatches' contexts held, the share their
full-attention layers attended to after selection: the spans'
``selected_rows`` over their ``context_rows`` (what the counters
``serving_dsa_selected_rows_total`` / ``serving_dsa_context_rows_total``
sum), chunk programs and decode steps together. ``None`` without such
spans."""

from benchmark.readers import dsa_steps


def read(ctx: dict):
    chosen = held = 0.0
    for program in ("prefill_chunk", "decode"):
        for args, _ in dsa_steps.totals(ctx, program) or ():
            chosen += float(args.get("selected_rows", 0))
            held += float(args.get("context_rows", 0))
    return 100.0 * chosen / held if held else None

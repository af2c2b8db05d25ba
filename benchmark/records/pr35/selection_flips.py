"""How often the bfloat16 program's selected set differs from the float32
reference's, and what that moves: the rows at the top-k-th index score.

The program (bfloat16 operands, the chunk program with all logits) and
the plain reference (float32) run over the same seeded tokens with the
cell's seeded weights, a prompt of CHUNKS chunks. Per full-attention
layer: the share of the rows whose selection bites (position >= top-k)
that select another set than the reference, and of their selected rows
that differ. Per token: the gap by which the program's best id lies below
the reference's best in the reference's logits, split by whether the
selection bites at the token's position (before it, only the router's
near-ties and rounding separate the two).

    python3 benchmark/records/pr35/selection_flips.py SEED [CHUNKS] [REHEARSE]
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.getcwd())


def main():
    import jax
    import jax.numpy as jnp

    from benchmark import program, weights_by_range
    from benchmark import run as bench_run
    from benchmark.manifest import ROOT, Manifest, load_module

    seed = int(sys.argv[1])
    chunks = int((sys.argv[2:] or ["6"])[0])
    rehearse = int((sys.argv[3:] or ["0"])[0])
    bench_run.prepare(rehearse)
    ns = argparse.Namespace(workload="dots3-serve-longctx", seed=seed,
                            seconds=45.0, trace=0, rehearse=rehearse)
    env = bench_run.Env(Manifest(ROOT), ns)
    kind = load_module(os.path.join(ROOT, "benchmark", "kinds",
                                    "serve_state.py"))
    model, ref, ref_cfg, spec = program.build_model(
        env, program.train_config(env))
    kind._apply_share(env, model)
    params = weights_by_range.make_params(spec, seed, model.param_dtype)
    e = env.pick(env.traffic, "engine")
    chunk, bs = e["prefill_chunk"], e["block_size"]
    p = min(chunks * chunk, e["prompt_len"] // chunk * chunk)
    top_k = ref_cfg["index_topk"]
    toks = np.random.RandomState(seed % 2**31).randint(
        110, ref_cfg["vocab_size"], p).astype(np.int32)

    masks = []
    real = model._select

    def spy(scores, k, live):
        m = real(scores, k, live)
        masks.append(m)
        return m

    model._select = spy
    nb = p // bs
    specs = model.state_specs(slots=1, num_blocks=1 + nb, block_size=bs)
    state = {k: jnp.zeros(v["shape"], v["dtype"]) for k, v in specs.items()}
    table = np.zeros((-(-e["prompt_len"] // chunk) * chunk // bs,), np.int32)
    table[:nb] = 1 + np.arange(nb)

    def chunk_fn(prm, st, ids, start, cb):
        masks.clear()
        out = model.prefill_chunk(prm, st, ids, chunk, start, 0, table, cb,
                                  with_logits=True)
        return out, [m[:, :p] for m in masks]

    fn = jax.jit(chunk_fn)
    got_logits, got_masks = [], []
    for start in range(0, p, chunk):
        cb = table[start // bs:start // bs + chunk // bs]
        out, ms = fn(params, state, toks[None, start:start + chunk], start,
                     cb)
        state = {k: out[k] for k in state}
        got_logits.append(np.asarray(out["logits"], np.float32))
        got_masks.append([np.asarray(m) for m in ms])
    got = np.concatenate(got_logits)                        # [p, V]
    layers = len(got_masks[0])
    del state, out

    bites = np.arange(p) >= top_k
    for i in range(layers):
        mine = np.concatenate([c[i] for c in got_masks])    # [p, p]
        want = np.asarray(jax.jit(
            lambda prm, x: ref.selection(ref_cfg, prm, x, i))(
                params, jnp.asarray(toks)))
        assert (mine.sum(-1) == want.sum(-1)).all()
        differ = (mine & ~want).sum(-1)
        print(json.dumps({
            "full_layer": i,
            "rows_where_selection_bites": int(bites.sum()),
            "rows_selecting_another_set_pct": round(100.0 * float(
                np.mean(differ[bites] > 0)), 2) if bites.any() else None,
            "selected_rows_that_differ_pct": round(100.0 * float(
                differ[bites].sum()) / max(1, int(want[bites].sum())), 4),
            "most_in_one_row": int(differ.max()),
            "rows_differing_before_it_bites": int(
                (differ[~bites] > 0).sum())}), flush=True)
    want = np.asarray(jax.jit(lambda prm, x: ref.logits(ref_cfg, prm, x))(
        params, jnp.asarray(toks)), np.float32)
    chosen = got.argmax(-1)
    gap = want.max(-1) - want[np.arange(p), chosen]
    err = np.abs(got - want).mean(-1)

    def mean(x, where):
        return float(x[where].mean()) if where.any() else None

    print(json.dumps({"selection_flips": {
        "tokens": p, "seed": seed, "top_k": top_k,
        "gap_mean": float(gap.mean()),
        "gap_mean_where_selection_bites": mean(gap, bites),
        "gap_mean_before_it_bites": mean(gap, ~bites),
        "abs_logit_err_mean_where_it_bites": mean(err, bites),
        "abs_logit_err_mean_before": mean(err, ~bites),
        "argmax_differs_pct": round(100.0 * float(
            np.mean(chosen != want.argmax(-1))), 2),
        "logit_std": float(want.std())}}), flush=True)
    env.cleanup()


if __name__ == "__main__":
    main()

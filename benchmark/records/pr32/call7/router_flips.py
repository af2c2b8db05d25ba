"""Why the program's logits lie 0.011-0.016 from the reference's on
average: the experts each token is routed to, program against reference,
over one prompt of two chunks.

The program (bfloat16 operands, the chunk program with all logits) and
the plain reference (float32) run over the same seeded tokens with the
cell's seeded weights. Per sparse layer: the share of tokens whose eight
experts are not the reference's eight, and of (token, pick) pairs that
differ. Per token: the gap by which the program's best id lies below the
reference's best in the reference's logits, split by whether any layer
routed the token otherwise.

    python3 benchmark/records/pr32/call7/router_flips.py SEED [REHEARSE]
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
    from distributed_tensorflow_example_tpu.ops import moe

    seed, rehearse = int(sys.argv[1]), int((sys.argv[2:] or ["0"])[0])
    bench_run.prepare(rehearse)
    ns = argparse.Namespace(workload="kimi-serve-backlog", seed=seed,
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
    p = 2 * chunk
    toks = np.random.RandomState(seed % 2**31).randint(
        110, ref_cfg["vocab_size"], p).astype(np.int32)

    # the program: two chunks through the state, every row's logits, and
    # the router's picks of every sparse layer (read where they are made)
    picked = []
    real = moe.sigmoid_top_k

    def spy(*a, **k):
        w, idx = real(*a, **k)
        picked.append(idx)
        return w, idx

    moe.sigmoid_top_k = spy
    nb = p // bs
    specs = model.state_specs(slots=1, num_blocks=1 + nb, block_size=bs)
    state = {k: jnp.zeros(v["shape"], v["dtype"]) for k, v in specs.items()}
    table = np.zeros((e["prompt_len"] // bs,), np.int32)
    table[:nb] = 1 + np.arange(nb)

    def chunk_fn(prm, st, ids, start, cb):
        picked.clear()
        out = model.prefill_chunk(prm, st, ids, chunk, start, 0, table, cb,
                                  with_logits=True)
        return out, list(picked)

    fn = jax.jit(chunk_fn)
    got_logits, got_picks = [], []
    for start in range(0, p, chunk):
        cb = table[start // bs:start // bs + chunk // bs]
        out, picks = fn(params, state, toks[None, start:start + chunk],
                        start, cb)
        state = {k: out[k] for k in state}
        got_logits.append(np.asarray(out["logits"], np.float32))
        got_picks.append([np.asarray(x) for x in picks])
    moe.sigmoid_top_k = real
    got = np.concatenate(got_logits)                        # [p, V]
    layers = len(got_picks[0])
    got_idx = [np.concatenate([c[i] for c in got_picks])
               for i in range(layers)]                      # [p, 8] each
    del state, out

    # the reference: float32, its own picks read from its only top_k
    want_picks = []
    real_top_k = jax.lax.top_k

    def spy_top_k(x, k):
        v, idx = real_top_k(x, k)
        want_picks.append(idx)
        return v, idx

    def ref_fn(prm, x):
        want_picks.clear()
        jax.lax.top_k = spy_top_k
        try:
            lg = ref.logits(ref_cfg, prm, x)
        finally:
            jax.lax.top_k = real_top_k
        return lg, list(want_picks)

    want, picks = jax.jit(ref_fn)(params, jnp.asarray(toks))
    want = np.asarray(want, np.float32)
    want_idx = [np.asarray(x) for x in picks]
    assert len(want_idx) == layers, (len(want_idx), layers)

    any_flip = np.zeros(p, bool)
    for i in range(layers):
        a, b = np.sort(got_idx[i], -1), np.sort(want_idx[i], -1)
        differ = np.array([len(set(x) - set(y)) for x, y in zip(a, b)])
        any_flip |= differ > 0
        print(json.dumps({
            "sparse_layer": i,
            "tokens_routed_otherwise_pct": round(
                100.0 * float(np.mean(differ > 0)), 2),
            "picks_that_differ_pct": round(
                100.0 * float(differ.sum()) / differ.size / a.shape[1], 3)}),
            flush=True)
    chosen = got.argmax(-1)
    gap = want.max(-1) - want[np.arange(p), chosen]
    err = np.abs(got - want).mean(-1)
    out = {"tokens": p, "seed": seed,
           "tokens_routed_otherwise_in_any_layer_pct": round(
               100.0 * float(any_flip.mean()), 2),
           "gap_mean": float(gap.mean()),
           "gap_mean_where_routed_otherwise": float(gap[any_flip].mean())
           if any_flip.any() else None,
           "gap_mean_where_routed_alike": float(gap[~any_flip].mean())
           if (~any_flip).any() else None,
           "abs_logit_err_mean_routed_otherwise": float(err[any_flip].mean())
           if any_flip.any() else None,
           "abs_logit_err_mean_routed_alike": float(err[~any_flip].mean())
           if (~any_flip).any() else None,
           "argmax_differs_pct": round(100.0 * float(
               np.mean(chosen != want.argmax(-1))), 2),
           "logit_std": float(want.std())}
    print(json.dumps({"router_flips": out}), flush=True)
    env.cleanup()


if __name__ == "__main__":
    main()

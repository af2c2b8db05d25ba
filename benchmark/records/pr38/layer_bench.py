"""dots3-note-prev's chunk expert layer alone, at the cell's shapes (1,024
rows x top-8 of 256 sigmoid-routed experts, 32 held, [5120, 1536] and
[1536, 5120] bfloat16): device ms a call of the whole jitted
``ops/moe.moe_dropless`` and of its ``ragged-dot`` operations, from a
profiler capture of five calls (``experiments/flash_sweep._capture``),
under

- ``parent``: XLA's own tile, every ``T x k`` row (the parent's program);
- ``tile``: the tiles of ``ragged_tiling``, every row ((a) alone);
- ``bounded:<combine>``: those tiles over the first ``pair_bound`` rows
  of the order ((a) + (b)), the rows added up by a ``gather`` from the
  rows and one zero row (the module's own ``_combine_bounded`` since
  call 2; in the rows of calls 1 and 2 the module's own was the
  scatter-add and the names say which ran), by a ``scatter``-add into
  [T, H], by a ``onehot`` [T, bound] float32 matmul at the highest
  precision, or by the same matmul in three bfloat16 passes
  (``onehot3``: meant to lose nothing, read 6e-3 off on the chip: XLA
  merges the three products' operands before it multiplies);
- ``bounded:gather`` at the bounds 1,280 and 1,536 (information for
  ROADMAP S15's "a tighter bound than 2 x");
- ``overflow``: a router that sends every pick to held experts, so the
  ``lax.cond`` takes the whole-width branch (what the fallback costs).

A row names tiles when it overrides the module's rule: ``TILES`` (argv 2,
``gate_tile/down_tile``) lets one call price a tile the rule does not
give yet. ``max_abs_diff`` is against the parent's result under the same router.

    python3 benchmark/records/pr38/layer_bench.py [OUT.jsonl [G/D ...]]
"""

import json
import os
import sys

sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "experiments")]

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from distributed_tensorflow_example_tpu.ops import moe      # noqa: E402
from flash_sweep import _capture                            # noqa: E402
from benchmark import trace_reduce                          # noqa: E402

TINY = jax.devices()[0].platform != "tpu"   # a rehearsal: does it run
T, H, F, E, HELD, K = ((64, 128, 128, 16, 2, 4) if TINY
                       else (1024, 5120, 1536, 256, 32, 8))
RULE = {"ragged_tiling": moe.ragged_tiling, "pair_bound": moe.pair_bound,
        "_combine_bounded": moe._combine_bounded}


def scatter_combine(out, at, w, top_k):
    """The weighted rows added into their rows of [T, H]."""
    scaled = out * w.reshape(-1)[at][:, None]
    return jnp.zeros((w.shape[0], out.shape[1]), out.dtype).at[
        at // top_k].add(scaled)


def onehot_combine(out, at, w, top_k):
    """[T, bound] of each pair's weight at its row, times the rows."""
    sel = jnp.where(
        (at // top_k)[None, :] == jnp.arange(w.shape[0])[:, None],
        w.reshape(-1)[at][None, :], 0.0)
    return jnp.dot(sel, out, precision=jax.lax.Precision.HIGHEST)


def onehot3_combine(out, at, w, top_k):
    """The same matmul in three bfloat16 passes: the 0 / 1 matrix is
    exact in bfloat16 and each weighted row is the sum of three bfloat16
    terms (8 + 8 + 8 bits of a float32's 24)."""
    scaled = out * w.reshape(-1)[at][:, None]
    sel = ((at // top_k)[None, :] == jnp.arange(w.shape[0])[:, None]
           ).astype(jnp.bfloat16)
    y = 0.0
    for _ in range(3):
        term = scaled.astype(jnp.bfloat16)
        y = y + jnp.dot(sel, term, preferred_element_type=jnp.float32)
        scaled = scaled - term.astype(jnp.float32)
    return y


def variants(tiles):
    """``(name, {module attribute: override})``; ``tiles``: ``(gate,
    down)`` tiles to force, or None for the module's rule."""
    forced = {}
    if tiles:
        def forced_tile(pairs, k, n, dtype):
            return tiles[k < n] if pairs % 128 == 0 else None
        forced = {"ragged_tiling": forced_tile}
    whole = {"pair_bound": lambda pairs, held, experts: pairs}
    yield "tile", {**forced, **whole}
    for name, fn in (("gather", None), ("scatter", scatter_combine),
                     ("onehot", onehot_combine),
                     ("onehot3", onehot3_combine)):
        yield f"bounded:{name}", {**forced, **(
            {"_combine_bounded": fn} if fn else {})}
    if not TINY:
        for bound in (1280, 1536):
            yield f"bounded:gather:{bound}", {
                **forced, "pair_bound": lambda p, h, e, b=bound: b}


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else None
    tile_sets = [None] + [tuple(a.split("/")) for a in sys.argv[2:]]
    k = jax.random.split(jax.random.key(38), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(k[0], (T, H))
    # near-uniform routing, as the cell's seeded weights give (call 1 drew
    # logits of s.d. 3.6 under a bias of 0.01: saturated sigmoids, the
    # bias picked, 591 held pairs in a few experts)
    router = jax.random.normal(k[1], (H, E)) * 0.002
    experts = {"gate": (jax.random.normal(k[2], (HELD, H, F)) * 0.02
                        ).astype(bf),
               "up": (jax.random.normal(k[3], (HELD, H, F)) * 0.02
                      ).astype(bf),
               "down": (jax.random.normal(k[4], (HELD, F, H)) * 0.02
                        ).astype(bf)}
    bias = jax.random.normal(k[5], (E,)) * 0.001
    # every pick a held expert: the bias lifts the held 32 over the rest
    all_held = bias.at[:HELD].add(4.0)

    rows, bases = [], {}

    def run(name, overrides, tiles=None, b=bias):
        for attr, fn in {**RULE, **overrides}.items():
            setattr(moe, attr, fn)

        def layer(x, router, experts, bias):    # a trace of its own a run
            return moe.moe_dropless(x, router, experts, top_k=K,
                                    scores="sigmoid", select_bias=bias,
                                    scale=2.5)
        rows_log = {}
        with moe.tile_log(rows_log) as logged:
            call = jax.jit(layer)
            y, held_rows = call(x, router, experts, b)
        red = _capture(call, (x, router, experts, b), 5)
        row = {
            "variant": name, "forced": tiles and "/".join(tiles),
            "tiles": dict(logged), **rows_log,
            "held_pairs": int(held_rows.sum()),
            "ms": round(red["busy_s"] / 5 * 1e3, 4),
            "ragged_dot_ms": round(trace_reduce.op_seconds(
                red, pattern="ragged-dot") / 5 * 1e3, 4),
            "top_ops": [[n, round(s / 5 * 1e3, 4)] for n, s in sorted(
                ((n, v["seconds"]) for n, v in red["all_ops"].items()),
                key=lambda kv: -kv[1])[:8]],
            "max_abs_diff": (float(jnp.max(jnp.abs(y - bases[id(b)])))
                             if id(b) in bases else None),
            "device": jax.devices()[0].device_kind}
        bases.setdefault(id(b), y)      # the parent's, first a bias
        rows.append(row)
        print(json.dumps(row), flush=True)

    parent = {"ragged_tiling": lambda *a: None,
              "pair_bound": lambda pairs, held, experts: pairs}
    run("parent", parent)
    for tiles in tile_sets:
        for name, overrides in variants(tiles):
            run(name, overrides, tiles)
    run("overflow:parent", parent, b=all_held)
    run("overflow", {}, b=all_held)
    for attr, fn in RULE.items():
        setattr(moe, attr, fn)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        with open(out_path, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    main()

"""Inputs from the seed: training corpora and serving schedules.

The corpus and the masked-LM masking are copies of the arithmetic in the
program's ``data/bert_data.py`` (``synthetic_corpus``,
``apply_mlm_masking``), kept here so that no later PR can change the
work a cell does; the originals are listed in PERF.md for deletion.
Every seed gives the same SIZES (sequence lengths, batch, request
lengths, arrival gaps) in another order with other token values, so the
seed never changes the amount of work.
"""

from __future__ import annotations

import statistics

import numpy as np

PAD, CLS, SEP, MASK = 0, 101, 102, 103
FIRST_REGULAR = 110            # ids below this are reserved/special


def rng(seed: int, stream: int = 0) -> np.random.RandomState:
    return np.random.RandomState([int(seed) % (2 ** 32), int(seed) >> 32,
                                  stream])


def synthetic_corpus(num_seqs: int, seq_len: int, vocab_size: int,
                     rs: np.random.RandomState) -> np.ndarray:
    """[N, S] int32: Zipf tokens with a deterministic bigram structure
    (token t is followed by (t*7+11)%V with probability 0.5)."""
    v_eff = vocab_size - FIRST_REGULAR

    def zipf_draw(n):
        return (np.minimum(rs.zipf(1.3, size=n), v_eff) - 1) + FIRST_REGULAR

    seqs = np.empty((num_seqs, seq_len), np.int32)
    seqs[:, 0] = CLS
    cur = zipf_draw(num_seqs)
    seqs[:, 1] = cur
    for j in range(2, seq_len - 1):
        follow = (cur * 7 + 11) % v_eff + FIRST_REGULAR
        take = rs.rand(num_seqs) < 0.5
        cur = np.where(take, follow, zipf_draw(num_seqs)).astype(np.int32)
        seqs[:, j] = cur
    seqs[:, -1] = SEP
    return seqs


def mlm_masking(seqs: np.ndarray, vocab_size: int, max_predictions: int,
                mask_prob: float, rs: np.random.RandomState) -> dict:
    """BERT masking into the static-shape batch layout: 15 % of the
    positions, of which 80 % -> [MASK], 10 % -> a random token, 10 % kept."""
    n, s = seqs.shape
    m = max_predictions
    maskable = ~np.isin(seqs, (PAD, CLS, SEP, MASK))
    cand = maskable.sum(axis=1)
    k = np.minimum.reduce([
        np.full(n, m), cand,
        np.maximum(1, np.round(cand * mask_prob).astype(np.int64))])
    k = np.where(cand == 0, 0, k)
    keys = rs.rand(n, s) + np.where(maskable, 0.0, 10.0)
    order = np.argsort(keys, axis=1)[:, :m].astype(np.int32)
    sel = np.arange(m)[None, :] < k[:, None]
    positions = np.where(sel, order, 0).astype(np.int32)
    orig = np.take_along_axis(seqs, positions, axis=1)
    decide = rs.rand(n, m)
    rand_tok = rs.randint(FIRST_REGULAR, vocab_size, size=(n, m))
    new_tok = np.where(decide < 0.8, MASK,
                       np.where(decide < 0.9, rand_tok, orig)).astype(np.int32)
    input_ids = seqs.copy()
    rows = np.broadcast_to(np.arange(n)[:, None], (n, m))[sel]
    input_ids[rows, positions[sel]] = new_tok[sel]
    return {"input_ids": input_ids.astype(np.int32),
            "token_type_ids": np.zeros((n, s), np.int32),
            "attention_mask": (seqs != PAD).astype(np.int32),
            "masked_positions": positions,
            "masked_labels": np.where(sel, orig, 0).astype(np.int32),
            "masked_weights": sel.astype(np.float32)}


def train_arrays(data: dict, vocab_size: int, seed: int) -> dict:
    """The training set of one run, as the program's loader takes it."""
    seqs = synthetic_corpus(data["num_seqs"], data["seq_len"], vocab_size,
                            rng(seed, 1))
    if data["objective"] == "causal_lm":
        return {"input_ids": seqs,
                "attention_mask": (seqs != PAD).astype(np.int32)}
    if data["objective"] == "masked_lm":
        return mlm_masking(seqs, vocab_size, data["max_predictions"],
                           data["mask_prob"], rng(seed, 2))
    raise ValueError(f"unknown objective {data['objective']!r}")


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def lognormal_grid(n: int, median: float, sigma: float, lo: int, hi: int
                   ) -> np.ndarray:
    """``n`` lengths at the lognormal's evenly spaced quantiles, clipped:
    the same multiset for every seed."""
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = median * np.exp(sigma * z)
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def exponential_grid(n: int, mean: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the exponential's evenly spaced
    quantiles, rescaled to sum to exactly ``n * mean``."""
    p = (np.arange(n) + 0.5) / n
    g = -np.log1p(-p)
    return g * (n * mean / g.sum())


def serving_requests(t: dict, vocab_size: int, rs: np.random.RandomState,
                     count: int, first_idx: int = 0) -> list[dict]:
    """``count`` requests: the fixed grids of prompt and output lengths,
    each shuffled by ``rs``, with prompt tokens drawn from it (no two
    prompts share a prefix beyond chance)."""
    pl = lognormal_grid(count, **t["prompt_tokens"])
    ol = lognormal_grid(count, **t["output_tokens"])
    rs.shuffle(pl)
    rs.shuffle(ol)
    return [{"idx": first_idx + i,
             "prompt": rs.randint(FIRST_REGULAR, vocab_size,
                                  int(pl[i])).tolist(),
             "max_new": int(ol[i])} for i in range(count)]


def open_schedule(t: dict, vocab_size: int, seed: int,
                  segments: list[float]) -> list[dict]:
    """Open loop: Poisson-like arrivals at ``rate_rps``. Each segment (the
    ramp, the window, the drain) is a population of its own: the
    exponential's quantile grid of gaps summing to exactly the segment's
    length and the lognormals' grids of lengths, shuffled by the seed. So
    every seed's window holds the same number of requests with the same
    multiset of sizes and gaps, in another order."""
    rs = rng(seed, 3)
    out: list[dict] = []
    start = 0.0
    for seconds in segments:
        count = int(round(t["rate_rps"] * seconds))
        reqs = serving_requests(t, vocab_size, rs, count, len(out))
        gaps = exponential_grid(count, seconds / count)
        rs.shuffle(gaps)
        # a request sits in the middle of its gap: the segment's first
        # arrival is not at its very start, its last not at its end
        due = start + np.cumsum(gaps) - gaps / 2.0
        for r, d in zip(reqs, due):
            r["due_s"] = float(d)
        out += reqs
        start += seconds
    return sorted(out, key=lambda r: r["due_s"])


def closed_schedule(t: dict, vocab_size: int, seed: int, count: int
                    ) -> list[list[dict]]:
    """Closed loop: ``clients`` queues; each client posts its next request
    when the last returns."""
    reqs = serving_requests(t, vocab_size, rng(seed, 3), count)
    return [reqs[c::t["clients"]] for c in range(t["clients"])]

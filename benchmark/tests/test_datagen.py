import json

import numpy as np

from benchmark import datagen

MIX = {"rate_rps": 9.5, "clients": 128,
       "prompt_tokens": {"median": 128, "sigma": 0.8, "lo": 16, "hi": 512},
       "output_tokens": {"median": 64, "sigma": 0.7, "lo": 8, "hi": 256}}


SEGMENTS = [15.0, 45.0, 30.0]


def test_schedule_is_reproducible_from_the_seed():
    a = datagen.open_schedule(MIX, 50257, 2**31 + 12345, SEGMENTS)
    b = datagen.open_schedule(MIX, 50257, 2**31 + 12345, SEGMENTS)
    assert json.dumps(a) == json.dumps(b)
    c = datagen.open_schedule(MIX, 50257, 2**31 + 12346, SEGMENTS)
    assert json.dumps(a) != json.dumps(c)


def test_every_seed_offers_the_same_work_in_another_order():
    a = datagen.open_schedule(MIX, 50257, 1, SEGMENTS)
    b = datagen.open_schedule(MIX, 50257, 3_000_000_000, SEGMENTS)

    def window(s):
        return [r for r in s if 15.0 <= r["due_s"] < 60.0]

    wa, wb = window(a), window(b)
    # the window holds the same number of requests, the same multiset of
    # prompt and output lengths and the same gaps, for every seed
    assert len(wa) == len(wb) == round(9.5 * 45)
    assert sorted(r["max_new"] for r in wa) == sorted(
        r["max_new"] for r in wb)
    assert sorted(len(r["prompt"]) for r in wa) == sorted(
        len(r["prompt"]) for r in wb)
    assert [r["max_new"] for r in wa] != [r["max_new"] for r in wb]
    assert len(a) == len(b) == round(9.5 * 15) + len(wa) + round(9.5 * 30)
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    assert len({r["idx"] for r in a}) == len(a)


def test_lengths_follow_the_stated_lognormals():
    g = datagen.lognormal_grid(2000, 64, 0.7, 8, 256)
    assert g.min() >= 8 and g.max() <= 256
    assert abs(np.median(g) - 64) <= 1
    assert 75 < g.mean() < 85          # exp(0.7^2/2) * 64 = 81.8, clipped
    e = datagen.exponential_grid(1000, 0.1)
    assert abs(e.sum() - 100.0) < 1e-9 and e.min() > 0


def test_prompt_tokens_avoid_the_special_ids():
    reqs = datagen.serving_requests(MIX, 50257, datagen.rng(7, 3), 50)
    toks = np.concatenate([r["prompt"] for r in reqs])
    assert toks.min() >= datagen.FIRST_REGULAR and toks.max() < 50257


def test_closed_schedule_deals_requests_to_clients():
    qs = datagen.closed_schedule(MIX, 50257, 9, 128 * 3)
    assert len(qs) == 128 and all(len(q) == 3 for q in qs)
    assert sorted(r["idx"] for q in qs for r in q) == list(range(384))


def test_corpus_and_masking_are_seeded_and_well_formed():
    data = {"objective": "masked_lm", "num_seqs": 32, "seq_len": 64,
            "batch_size": 8, "max_predictions": 10, "mask_prob": 0.15}
    a = datagen.train_arrays(data, 30522, 2**31 + 5)
    b = datagen.train_arrays(data, 30522, 2**31 + 5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert a["input_ids"].shape == (32, 64)
    assert a["masked_positions"].shape == (32, 10)
    # round(62 * 0.15) = 9 predictions a row, the rest padded with weight 0
    assert np.all(a["masked_weights"].sum(axis=1) == 9)
    picked = np.take_along_axis(a["input_ids"], a["masked_positions"], 1)
    changed = (picked != a["masked_labels"]) & (a["masked_weights"] > 0)
    assert 0.7 < changed.sum() / a["masked_weights"].sum() <= 1.0
    lm = datagen.train_arrays({"objective": "causal_lm", "num_seqs": 4,
                               "seq_len": 32, "batch_size": 2}, 50257, 1)
    assert lm["input_ids"][:, 0].tolist() == [datagen.CLS] * 4
    assert lm["attention_mask"].all()

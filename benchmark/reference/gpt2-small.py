"""Plain reference of GPT-2 (Radford et al. 2019; huggingface
openai-community/gpt2): learned token and position embeddings, pre-LN
blocks, tanh-approximated GELU, final LayerNorm, LM head tied to the
token embedding. float32 throughout at ``highest`` matmul precision.

Departures from the published model, all shared with the program so the
two compute the same function: q, k, v are three matrices (the published
``c_attn`` is their concatenation); no dropout (the cells train without
it, see the configuration's ``reduced``). The program's LayerNorm epsilon
is 1e-6 against the published 1e-5 kept here: a relative difference of
5e-6 in a normalised activation, far under bf16's 4e-3.
"""

import jax.numpy as jnp

from benchmark import refmath as rm

FAMILY = "gpt"


def param_spec(cfg: dict) -> dict:
    h, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    inner = cfg.get("n_inner") or 4 * h
    spec = {"wte/table": ((v, h), "normal"), "wpe/table": ((p, h), "normal"),
            "ln_f/scale": ((h,), "scale"), "ln_f/bias": ((h,), "bias")}
    for i in range(cfg["n_layer"]):
        pre = f"layer_{i}"
        for ln in ("ln1", "ln2"):
            spec[f"{pre}/{ln}/scale"] = ((h,), "scale")
            spec[f"{pre}/{ln}/bias"] = ((h,), "bias")
        for m in "qkvo":
            spec[f"{pre}/attn/{m}/kernel"] = ((h, h), "glorot")
            spec[f"{pre}/attn/{m}/bias"] = ((h,), "bias")
        spec[f"{pre}/ffn/in/kernel"] = ((h, inner), "glorot")
        spec[f"{pre}/ffn/in/bias"] = ((inner,), "bias")
        spec[f"{pre}/ffn/out/kernel"] = ((inner, h), "glorot")
        spec[f"{pre}/ffn/out/bias"] = ((h,), "bias")
    return spec


def logits(cfg: dict, params, ids, mask=None, precision: str = "f32"):
    """[B, S] token ids -> [B, S, V] next-token logits."""
    eps = cfg["layer_norm_epsilon"]
    _, s = ids.shape
    mask = jnp.ones_like(ids) if mask is None else mask
    h = params["wte"]["table"][ids] + params["wpe"]["table"][:s][None]
    for i in range(cfg["n_layer"]):
        lp = params[f"layer_{i}"]
        h = h + rm.attention(lp["attn"], rm.layernorm(lp["ln1"], h, eps),
                             mask, cfg["n_head"], True, precision)
        f = rm.gelu_tanh(rm.dense(lp["ffn"]["in"],
                                  rm.layernorm(lp["ln2"], h, eps),
                                  precision))
        h = h + rm.dense(lp["ffn"]["out"], f, precision)
    h = rm.layernorm(params["ln_f"], h, eps)
    return rm.einsum("bsh,vh->bsv", h, params["wte"]["table"], precision)


def loss_sums(cfg: dict, params, batch, precision: str = "f32"):
    """Next-token loss of a block of rows: (sum of weighted nll, sum of
    weights); padding carries no loss."""
    ids = batch["input_ids"]
    mask = batch.get("attention_mask", jnp.ones_like(ids))
    lg = logits(cfg, params, ids, mask, precision)[:, :-1]
    return rm.weighted_nll(lg, ids[:, 1:], mask[:, 1:].astype(jnp.float32))


def train_flops_per_token(cfg: dict, seq_len: int, **_) -> float:
    """Required FLOPs per trained token, forward and backward (3x the
    forward), causal attention counted at half the square, embeddings'
    lookups free, nothing recomputed."""
    h, v, layers = cfg["n_embd"], cfg["vocab_size"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * h
    per_layer = 2 * (4 * h * h + 2 * h * inner) + 2 * seq_len * h
    return 3.0 * (layers * per_layer + 2 * h * v)

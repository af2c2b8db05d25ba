"""Plain reference of BERT (Devlin et al., arXiv:1810.04805; huggingface
google-bert/bert-base-uncased): word + position + type embeddings with a
LayerNorm, post-LN encoder blocks, masked-LM head (dense, GELU,
LayerNorm, decoder tied to the word embedding plus a bias). float32
throughout at ``highest`` matmul precision.

Departures from the published model, all shared with the program: GELU is
the tanh approximation (published: erf); there is no pooler and no
next-sentence head (the program's model has none, so the loss is the
masked-LM loss alone); no dropout (see the configuration's ``reduced``).
The program's LayerNorm epsilon is 1e-6 against the published 1e-12 kept
here: 5e-7 relative in a normalised activation.
"""

import jax.numpy as jnp

from benchmark import refmath as rm

FAMILY = "bert"


def param_spec(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    inner = cfg["intermediate_size"]
    spec = {"embed/word/table": ((v, h), "normal"),
            "embed/pos/table": ((cfg["max_position_embeddings"], h),
                                "normal"),
            "embed/type/table": ((cfg["type_vocab_size"], h), "normal"),
            "embed_ln/scale": ((h,), "scale"),
            "embed_ln/bias": ((h,), "bias"),
            "mlm/transform/kernel": ((h, h), "glorot"),
            "mlm/transform/bias": ((h,), "bias"),
            "mlm/ln/scale": ((h,), "scale"), "mlm/ln/bias": ((h,), "bias"),
            "mlm/bias": ((v,), "bias")}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer_{i}"
        for ln in ("attn_ln", "ffn_ln"):
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


def mlm_logits(cfg: dict, params, batch, precision: str = "f32"):
    """Masked-position logits [B, M, V]."""
    eps = cfg["layer_norm_eps"]
    ids = batch["input_ids"]
    _, s = ids.shape
    mask = batch.get("attention_mask", jnp.ones_like(ids))
    types = batch.get("token_type_ids", jnp.zeros_like(ids))
    e = params["embed"]
    h = (e["word"]["table"][ids] + e["pos"]["table"][:s][None]
         + e["type"]["table"][types])
    h = rm.layernorm(params["embed_ln"], h, eps)
    for i in range(cfg["num_hidden_layers"]):
        lp = params[f"layer_{i}"]
        a = rm.attention(lp["attn"], h, mask, cfg["num_attention_heads"],
                         False, precision)
        h = rm.layernorm(lp["attn_ln"], h + a, eps)
        f = rm.gelu_tanh(rm.dense(lp["ffn"]["in"], h, precision))
        h = rm.layernorm(lp["ffn_ln"],
                         h + rm.dense(lp["ffn"]["out"], f, precision), eps)
    picked = jnp.take_along_axis(h, batch["masked_positions"][..., None],
                                 axis=1)
    t = rm.gelu_tanh(rm.dense(params["mlm"]["transform"], picked, precision))
    t = rm.layernorm(params["mlm"]["ln"], t, eps)
    return (rm.einsum("bmh,vh->bmv", t, e["word"]["table"], precision)
            + params["mlm"]["bias"])


def loss_sums(cfg: dict, params, batch, precision: str = "f32"):
    return rm.weighted_nll(mlm_logits(cfg, params, batch, precision),
                           batch["masked_labels"],
                           batch["masked_weights"].astype(jnp.float32))


def train_flops_per_token(cfg: dict, seq_len: int,
                          max_predictions: int = 20, **_) -> float:
    """Required FLOPs per trained token, forward and backward (3x the
    forward): full attention, the masked-LM head on ``max_predictions``
    positions of each sequence, embeddings' lookups free."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    inner = cfg["intermediate_size"]
    per_layer = 2 * (4 * h * h + 2 * h * inner) + 4 * seq_len * h
    head = 2 * (h * h + h * v) * max_predictions / seq_len
    return 3.0 * (cfg["num_hidden_layers"] * per_layer + head)

"""Plain reference of SDAR-30B-A3B-Chat's decoder (huggingface
JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``) and of its generation
by diffusion over blocks (the family's published ``generate.py``).
float32 throughout at ``highest`` matmul precision; no kernel, no cache,
no sort, no capacity: a dense loop over the experts and the full
block-causal mask.

No bias anywhere. Block, pre-norm::

    h = x + Attn(RMSNorm(x))        y = h + MoE(RMSNorm(h))

``rms_norm_eps`` 1e-6; a final RMSNorm; an untied head [hidden, vocab].

- Attention: ``q = W_q x`` as 32 heads x 128, ``k = W_k x``, ``v = W_v x``
  as 4 heads x 128; per-head RMSNorm (a learned scale of 128) on q and on
  k, then rotary positions (rotate-half over the whole 128, ``rope_theta``
  1e6, no scaling); scores ``q k^T / sqrt(128)``; query head ``h`` reads
  KV head ``h // 8``; mask ``M[i, j] = 1 iff floor(j / B) <= floor(i / B)``
  (bidirectional inside a block of B positions, causal across blocks;
  B = 1 is the causal mask); ``o = W_o concat(heads)``, W_o [4096, 2048].
- Experts: ``p = softmax(W_r x)`` over all 128; the 8 largest, their
  weights renormalised to sum to 1 (``norm_topk_prob``);
  ``y = sum_e w_e W_down,e (silu(W_gate,e x) * W_up,e x)``, width 768;
  every layer sparse, no shared expert, no token ever dropped.
- Generation: the sequence is cut into blocks of B positions from
  position 0. Whole blocks of the prompt are prefilled under the mask and
  their K/V kept. Each further block starts as its known prompt tokens
  and ``[MASK]`` elsewhere. A denoising forward runs the block's B
  positions against all earlier blocks and the block itself and gives
  each masked position a candidate (the argmax) and a confidence (its
  softmax probability); ``low_confidence_dynamic``: positions whose
  confidence exceeds the threshold are committed, and if fewer than
  ``n_s = B / denoising_steps`` did, the ``n_s`` most confident are. When
  no mask is left, one commit forward over the block's final tokens
  stores its K/V and the next block begins. Without a cache that is
  :func:`block_step`: the whole forward over ``[prefix ; block]``, read
  at the block's positions.

Departures from the published model, shared with the program: the config
gives neither block length nor schedule; the configuration's ``assumed``
sets them. A configuration that holds a share of the experts
(``experts_held`` from ``first_expert``) leaves out what the absent
experts would add, here as in the program.
"""

import math

import jax
import jax.numpy as jnp

from benchmark import refmath as rm

FAMILY = "block_diffusion_moe_decoder"


def _sizes(cfg: dict) -> dict:
    held = cfg.get("experts_held") or cfg["num_experts"]
    return dict(h=cfg["hidden_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                nh=cfg["num_attention_heads"],
                kvh=cfg["num_key_value_heads"], d=cfg["head_dim"],
                e=cfg["num_experts"], k=cfg["num_experts_per_tok"],
                f=cfg["moe_intermediate_size"], held=held,
                first=cfg.get("first_expert", 0),
                eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
                block=cfg["assumed"]["block_length"])


def param_spec(cfg: dict) -> dict:
    z = _sizes(cfg)
    h, qd, kd = z["h"], z["nh"] * z["d"], z["kvh"] * z["d"]
    spec = {"embed": ((z["v"], h), "normal"), "norm_f": ((h,), "scale"),
            "head": ((h, z["v"]), "glorot")}
    for i in range(z["layers"]):
        pre = f"layers/{i}"
        spec.update({
            f"{pre}/attn_norm": ((h,), "scale"),
            f"{pre}/attn/wq": ((h, qd), "glorot"),
            f"{pre}/attn/wk": ((h, kd), "glorot"),
            f"{pre}/attn/wv": ((h, kd), "glorot"),
            f"{pre}/attn/wo": ((qd, h), "glorot"),
            f"{pre}/attn/q_norm": ((z["d"],), "scale"),
            f"{pre}/attn/k_norm": ((z["d"],), "scale"),
            f"{pre}/ffn_norm": ((h,), "scale"),
            f"{pre}/moe/router": ((h, z["e"]), "glorot"),
            f"{pre}/moe/gate": ((z["held"], h, z["f"]), "glorot"),
            f"{pre}/moe/up": ((z["held"], h, z["f"]), "glorot"),
            f"{pre}/moe/down": ((z["held"], z["f"], h), "glorot"),
        })
    return spec


def rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rope(x, theta):
    """[S, heads, D], position = row index; rotate-half."""
    s, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def attention(z, ap, x, block, precision):
    s = x.shape[0]
    nh, kvh, d = z["nh"], z["kvh"], z["d"]
    q = rm.einsum("si,io->so", x, ap["wq"], precision).reshape(s, nh, d)
    k = rm.einsum("si,io->so", x, ap["wk"], precision).reshape(s, kvh, d)
    v = rm.einsum("si,io->so", x, ap["wv"], precision).reshape(s, kvh, d)
    q = rope(rms(q, ap["q_norm"], z["eps"]), z["theta"])
    k = rope(rms(k, ap["k_norm"], z["eps"]), z["theta"])
    pos = jnp.arange(s)
    allowed = (pos[None, :] // block) <= (pos[:, None] // block)
    group = nh // kvh
    outs = []
    for g in range(kvh):            # a KV head at a time: [group, S, S]
        qg = q[:, g * group:(g + 1) * group]
        sc = rm.einsum("qhd,kd->hqk", qg, k[:, g], precision) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(allowed[None], sc, -1e30), axis=-1)
        outs.append(rm.einsum("hqk,kd->qhd", pr, v[:, g], precision))
    ctx = jnp.concatenate(outs, axis=1).reshape(s, nh * d)
    return rm.einsum("si,io->so", ctx, ap["wo"], precision)


def experts(z, mp, x, precision):
    """The held experts' part of the layer: every expert over every row,
    weighted by the row's renormalised top-k share of it (0 for most)."""
    p = jax.nn.softmax(rm.einsum("si,ie->se", x, mp["router"], precision),
                       axis=-1)
    w, idx = jax.lax.top_k(p, z["k"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    ids = z["first"] + jnp.arange(z["held"])
    share = jnp.sum(jnp.where(idx[None] == ids[:, None, None], w[None], 0.0),
                    axis=-1)                                # [held, S]

    def one(y, xs):
        gate, up, down, we = xs
        a = jax.nn.silu(rm.einsum("si,if->sf", x, gate, precision)) \
            * rm.einsum("si,if->sf", x, up, precision)
        return y + we[:, None] * rm.einsum("sf,fo->so", a, down,
                                           precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (mp["gate"], mp["up"], mp["down"], share))
    return y


def hidden(cfg: dict, params, tokens, mask_info=None,
           precision: str = "f32"):
    """[S] token ids -> [S, hidden] after the last layer (before the
    final norm). ``mask_info``: ``{"block_length": B}`` to override the
    configuration's."""
    z = _sizes(cfg)
    block = (mask_info or {}).get("block_length", z["block"])
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(z["layers"]):
        lp = params["layers"][str(i)]
        x = x + attention(z, lp["attn"], rms(x, lp["attn_norm"], z["eps"]),
                          block, precision)
        x = x + experts(z, lp["moe"], rms(x, lp["ffn_norm"], z["eps"]),
                        precision)
    return x


def head(cfg: dict, params, x, precision: str = "f32"):
    return rm.einsum("sh,hv->sv", rms(x, params["norm_f"],
                                      cfg["rms_norm_eps"]),
                     params["head"], precision)


def logits(cfg: dict, params, tokens, mask_info=None,
           precision: str = "f32"):
    """[S] token ids -> [S, V] float32 logits under the block-causal
    mask."""
    with jax.default_matmul_precision("highest"):
        return head(cfg, params,
                    hidden(cfg, params, tokens, mask_info, precision),
                    precision)


def block_step(cfg: dict, params, prefix_tokens, block_tokens,
               precision: str = "f32"):
    """A denoising (or commit) forward without a cache: the forward over
    ``[prefix ; block]`` read at the block's positions -> [B, V] logits.
    ``prefix_tokens`` are whole blocks."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.concatenate([prefix_tokens, block_tokens])
        x = hidden(cfg, params, tokens, None, precision)
        return head(cfg, params, x[-block_tokens.shape[0]:], precision)


def block_logits_at(cfg: dict, params, tokens, start, lanes: int,
                    precision: str = "f32"):
    """:func:`block_step` over a right-padded sequence: ``tokens`` [W]
    holds ``[prefix ; block ; anything]`` with the block at ``start``.
    What follows a block is invisible to it under the mask, so the
    logits at its positions are :func:`block_step`'s; one width compiles
    once, whatever the prefix's length."""
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, params, tokens, None, precision)
        x = jax.lax.dynamic_slice_in_dim(x, start, lanes, axis=0)
        return head(cfg, params, x, precision)

"""Plain reference of Kimi-Linear-48B-A3B-Instruct's layers (huggingface
moonshotai/Kimi-Linear-48B-A3B-Instruct, ``model_type: kimi_linear``;
Kimi Delta Attention: arXiv:2510.26692). float32 throughout at
``highest`` matmul precision; no kernel, no cache, no chunk, no sort: the
KDA recurrence a token at a time, MLA unabsorbed under the full causal
mask, a dense loop over the held experts.

Pre-norm, no bias anywhere; ``n = RMSNorm(x)`` (eps 1e-5)::

    h = x + Mixer(n)        y = h + FFN(RMSNorm(h))

a final RMSNorm and an untied head [hidden, vocab]. Layers are numbered
from 1 as in the config: ``full_attn_layers`` (4, 8, ..., 24, 27) mix by
MLA, all others by KDA; layer 1's FFN is dense (``first_k_dense_replace``
1), every other layer's is sparse.

- KDA layer (32 heads, ``d_k = d_v = 128``): ``q, k, v`` =
  SiLU(conv4(``W_q n``)), SiLU(conv4(``W_k n``)), SiLU(conv4(``W_v n``)),
  each hidden -> 4096, conv4 a causal depthwise convolution over time of
  kernel 4 (zeros before the first token); per head ``q``, ``k``
  L2-normalised, ``q`` scaled by ``128^-1/2``. Per head and channel a
  decay ``a_t = exp(-exp(A_log) * softplus(W_f2 W_f1 n + dt_bias))`` in
  (0, 1)^128 and per head a rate ``b_t = sigmoid(W_b n)``. State ``S`` in
  R^{128 x 128}, zero at the first token::

      S' = Diag(a_t) S_{t-1}
      S_t = S' + b_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t

  Output ``W_o [ RMSNorm_128(o_t) * sigmoid(W_g2 W_g1 n) ]``.
- MLA layer (``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128,
  ``qk_rope_head_dim`` 64, ``v_head_dim`` 128, ``mla_use_nope``: no rotary
  rotation is applied anywhere, the 64 "rope" dimensions are plain extra
  dimensions every head shares): ``q = W_q n`` as 32 x (128 + 64);
  ``c = W_kva n`` in R^{576}, ``c_kv = RMSNorm(c[:512])``, ``k_pe =
  c[512:]``; ``[k_nope_h ; v_h] = W_kvb c_kv`` as 32 x (128 + 128); scores
  ``(q_nope_h . k_nope_h,s + q_pe_h . k_pe_s) / sqrt(192)``, causal
  softmax, ``o_h = sum p v_h,s``, output ``W_o`` 4096 -> hidden.
- Sparse FFN: ``s = sigmoid(W_r m)`` over all 256 experts; the 8 largest
  of ``s + bias`` (the bias selects only); ``w_e = 2.446 * s_e /
  sum_{top-8} s``; ``y = sum_e w_e E_e(m) + E_shared(m)``, every expert
  ``W_down (silu(W_gate m) * W_up m)`` of width 1024; no token dropped,
  no grouping (``num_expert_group`` = ``topk_group`` = 1).
- Dense FFN (layer 1): the same gated SiLU at width 9216.

Departures from the published model, shared with the program:

- leaves the program fuses lie side by side here too: ``wqkv`` is
  ``[W_q | W_k | W_v]`` and ``conv`` their three convolutions;
- sizes the config does not give are the configuration's ``assumed``
  (flash-linear-attention's ``KimiDeltaAttention``): rank 128 of ``W_f1``
  and ``W_g1``, ``A_log`` a scalar a head, ``dt_bias`` a value a channel,
  no bias on the convolutions, 1e-6 under the L2 norm's root;
- a configuration that holds a share (``num_experts`` experts from
  ``share.first_expert`` of the ``published`` 256; ``vocab_size`` ids
  from ``share.first_vocab``) leaves out what the absent experts would
  add and embeds an id held elsewhere as zeros, here as in the program.
"""

import math

import jax
import jax.numpy as jnp

from benchmark import refmath as rm

FAMILY = "hybrid_linear_attention_moe_decoder"
_QUERY_ROWS = 2048


def _sizes(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    pub, share = cfg.get("published", {}), cfg.get("share", {})
    a = cfg["assumed"]
    layers = cfg["num_hidden_layers"]
    return dict(
        h=cfg["hidden_size"], v=cfg["vocab_size"], layers=layers,
        nh=lin["num_heads"], d=lin["head_dim"],
        conv=lin["short_conv_kernel_size"],
        mla=[i for i in lin["full_attn_layers"] if i <= layers],
        rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        pe=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        qh=cfg["num_attention_heads"],
        e=pub.get("num_experts", cfg["num_experts"]),
        held=cfg["num_experts"], first=share.get("first_expert", 0),
        first_vocab=share.get("first_vocab", 0),
        k=cfg["num_experts_per_token"], f=cfg["moe_intermediate_size"],
        shared=cfg["num_shared_experts"],
        scale=cfg["routed_scaling_factor"],
        dense=cfg["first_k_dense_replace"], dense_f=cfg["intermediate_size"],
        eps=cfg["rms_norm_eps"], gate_rank=a["kda_gate_rank"])


def param_spec(cfg: dict) -> dict:
    z = _sizes(cfg)
    h, qd = z["h"], z["nh"] * z["d"]
    spec = {"embed": ((z["v"], h), "normal"), "norm_f": ((h,), "scale"),
            "head": ((h, z["v"]), "glorot")}

    def gated(pre, width, *lead):
        return {f"{pre}/gate": ((*lead, h, width), "glorot"),
                f"{pre}/up": ((*lead, h, width), "glorot"),
                f"{pre}/down": ((*lead, width, h), "glorot")}

    for i in range(z["layers"]):
        pre = f"layers/{i}"
        spec.update({f"{pre}/attn_norm": ((h,), "scale"),
                     f"{pre}/ffn_norm": ((h,), "scale")})
        if i + 1 in z["mla"]:
            spec.update({
                f"{pre}/mla/wq": ((h, z["qh"] * (z["nope"] + z["pe"])),
                                  "glorot"),
                f"{pre}/mla/wkva": ((h, z["rank"] + z["pe"]), "glorot"),
                f"{pre}/mla/kv_norm": ((z["rank"],), "scale"),
                f"{pre}/mla/wkvb": ((z["rank"],
                                     z["qh"] * (z["nope"] + z["vd"])),
                                    "glorot"),
                f"{pre}/mla/wo": ((z["qh"] * z["vd"], h), "glorot")})
        else:
            spec.update({
                f"{pre}/kda/wqkv": ((h, 3 * qd), "glorot"),
                f"{pre}/kda/conv": ((z["conv"], 3 * qd), "glorot"),
                f"{pre}/kda/f_a": ((h, z["gate_rank"]), "glorot"),
                f"{pre}/kda/f_b": ((z["gate_rank"], qd), "glorot"),
                f"{pre}/kda/dt_bias": ((qd,), "dt_bias"),
                f"{pre}/kda/a_log": ((z["nh"],), "a_log"),
                f"{pre}/kda/wb": ((h, z["nh"]), "glorot"),
                f"{pre}/kda/g_a": ((h, z["gate_rank"]), "glorot"),
                f"{pre}/kda/g_b": ((z["gate_rank"], qd), "glorot"),
                f"{pre}/kda/o_norm": ((z["d"],), "scale"),
                f"{pre}/kda/wo": ((qd, h), "glorot")})
        if i < z["dense"]:
            spec.update(gated(f"{pre}/mlp", z["dense_f"]))
        else:
            spec.update({f"{pre}/moe/router": ((h, z["e"]), "glorot"),
                         f"{pre}/moe/router_bias": ((z["e"],), "bias"),
                         **gated(f"{pre}/moe", z["f"], z["held"]),
                         **gated(f"{pre}/moe/shared",
                                 z["shared"] * z["f"])})
    return spec


def rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def conv4(x, w):
    """[S, C] through a causal depthwise convolution, ``w`` [K, C] with
    ``w[K - 1]`` on the current token; zeros before the first."""
    s, k = x.shape[0], w.shape[0]
    xx = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), jnp.float32), x])
    return sum(w[j].astype(jnp.float32) * xx[j:j + s] for j in range(k))


def kda(z, kp, n, precision):
    """[S, hidden] normed rows through a KDA mixer, a token at a time."""
    s = n.shape[0]
    nh, d = z["nh"], z["d"]
    qkv = jax.nn.silu(conv4(rm.einsum("si,io->so", n, kp["wqkv"],
                                      precision), kp["conv"]))
    q, k, v = (t.reshape(s, nh, d) for t in jnp.split(qkv, 3, axis=-1))

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6)

    q, k = l2(q) * d ** -0.5, l2(k)
    f = rm.einsum("sr,ro->so", rm.einsum("si,ir->sr", n, kp["f_a"],
                                         precision), kp["f_b"], precision)
    a = jnp.exp(-jnp.exp(kp["a_log"].astype(jnp.float32))[None, :, None]
                * jax.nn.softplus(f + kp["dt_bias"].astype(jnp.float32)
                                  ).reshape(s, nh, d))
    b = jax.nn.sigmoid(rm.einsum("si,ih->sh", n, kp["wb"], precision))

    def token(state, xs):
        qt, kt, vt, at, bt = xs
        state = at[..., None] * state
        r = vt - jnp.einsum("hkv,hk->hv", state, kt,
                            precision=jax.lax.Precision.HIGHEST)
        state = state + (bt[:, None] * kt)[..., None] * r[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt,
                                 precision=jax.lax.Precision.HIGHEST)

    _, o = jax.lax.scan(token, jnp.zeros((nh, d, d), jnp.float32),
                        (q, k, v, a, b))
    gate = jax.nn.sigmoid(rm.einsum(
        "sr,ro->so", rm.einsum("si,ir->sr", n, kp["g_a"], precision),
        kp["g_b"], precision))
    o = rms(o, kp["o_norm"], z["eps"]).reshape(s, nh * d) * gate
    return rm.einsum("si,io->so", o, kp["wo"], precision)


def mla(z, mp, n, precision):
    """[S, hidden] normed rows through an MLA mixer: per-head K and V
    from the latent, the full causal mask, a head at a time."""
    s = n.shape[0]
    nh, nope, pe, vd, rank = z["qh"], z["nope"], z["pe"], z["vd"], z["rank"]
    q = rm.einsum("si,io->so", n, mp["wq"], precision).reshape(
        s, nh, nope + pe)
    c = rm.einsum("si,io->so", n, mp["wkva"], precision)
    c_kv, k_pe = rms(c[:, :rank], mp["kv_norm"], z["eps"]), c[:, rank:]
    kv = rm.einsum("sc,co->so", c_kv, mp["wkvb"], precision).reshape(
        s, nh, nope + vd)
    pos = jnp.arange(s)
    # a head at a time, and of a long sequence _QUERY_ROWS queries at a
    # time, each against every key: so that [queries, S] fits the chip
    rows = _QUERY_ROWS if s % _QUERY_ROWS == 0 else s

    def head(xs):
        qh, kvh = xs                        # [S, nope + pe], [S, nope + vd]

        def queries(ys):
            qr, qpos = ys
            sc = (rm.einsum("qd,kd->qk", qr[:, :nope], kvh[:, :nope],
                            precision)
                  + rm.einsum("qd,kd->qk", qr[:, nope:], k_pe, precision)
                  ) / math.sqrt(nope + pe)
            causal = pos[None, :] <= qpos[:, None]
            pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
            return rm.einsum("qk,kd->qd", pr, kvh[:, nope:], precision)

        return jax.lax.map(queries, (qh.reshape(s // rows, rows, -1),
                                     pos.reshape(s // rows, rows))
                           ).reshape(s, vd)

    ctx = jax.lax.map(head, (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))
    return rm.einsum("si,io->so", ctx.transpose(1, 0, 2).reshape(s, nh * vd),
                     mp["wo"], precision)


def gated(p, x, precision):
    return rm.einsum(
        "sf,fo->so",
        jax.nn.silu(rm.einsum("si,if->sf", x, p["gate"], precision))
        * rm.einsum("si,if->sf", x, p["up"], precision), p["down"],
        precision)


def routed(z, mp, x, precision):
    """The held experts' part of the layer: every held expert over every
    row, weighted by the row's share of it (0 for most)."""
    sc = jax.nn.sigmoid(rm.einsum("si,ie->se", x, mp["router"], precision))
    _, idx = jax.lax.top_k(sc + mp["router_bias"].astype(jnp.float32),
                           z["k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    w = z["scale"] * w / jnp.sum(w, axis=-1, keepdims=True)
    ids = z["first"] + jnp.arange(z["held"])
    share = jnp.sum(jnp.where(idx[None] == ids[:, None, None], w[None], 0.0),
                    axis=-1)                                # [held, S]

    def one(y, xs):
        gate, up, down, we = xs
        return y + we[:, None] * gated(
            {"gate": gate, "up": up, "down": down}, x, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (mp["gate"], mp["up"], mp["down"], share))
    return y


def layer(z, i, lp, x, precision):
    n = rms(x, lp["attn_norm"], z["eps"])
    x = x + (mla(z, lp["mla"], n, precision) if "mla" in lp
             else kda(z, lp["kda"], n, precision))
    m = rms(x, lp["ffn_norm"], z["eps"])
    if "mlp" in lp:
        return x + gated(lp["mlp"], m, precision)
    return x + routed(z, lp["moe"], m, precision) + gated(
        lp["moe"]["shared"], m, precision)


def embed(cfg: dict, params, tokens):
    z = _sizes(cfg)
    local = tokens - z["first_vocab"]
    mine = (local >= 0) & (local < z["v"])
    rows = params["embed"][jnp.clip(local, 0, z["v"] - 1)]
    return jnp.where(mine[:, None], rows.astype(jnp.float32), 0.0)


def hidden(cfg: dict, params, tokens, precision: str = "f32"):
    """[S] token ids -> [S, hidden] after the last layer (before the
    final norm)."""
    z = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        x = embed(cfg, params, tokens)
        for i in range(z["layers"]):
            x = layer(z, i, params["layers"][str(i)], x, precision)
        return x


def head(cfg: dict, params, x, precision: str = "f32"):
    """Hidden rows -> logits over the held slice of the vocabulary
    (column ``j`` is id ``share.first_vocab + j``)."""
    with jax.default_matmul_precision("highest"):
        return rm.einsum("sh,hv->sv", rms(x, params["norm_f"],
                                          cfg["rms_norm_eps"]),
                         params["head"], precision)


def logits(cfg: dict, params, tokens, precision: str = "f32"):
    """[S] token ids -> [S, V_held] float32 logits, causal."""
    return head(cfg, params, hidden(cfg, params, tokens, precision),
                precision)

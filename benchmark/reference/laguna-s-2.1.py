"""Plain reference of Laguna-S-2.1's language-model layers (huggingface
poolside/Laguna-S-2.1, ``model_type: laguna``, 48 layers, hidden 3,072).
float32 throughout at ``highest`` matmul precision; no kernel, no cache,
no chunk, no batching: every row against every earlier row (a window
layer's against the last 512), a head at a time, a dense loop over the
held experts. Long sequences are computed in blocks of query rows and the
experts are upcast one at a time, so that a 16 k-token request fits.

Pre-norm, no bias; ``x`` a token's residual, ``n = rms(x)`` (eps 1e-6)::

    h = x + Mixer(n)        y = h + FFN(rms(h))

a final RMSNorm and an untied head [hidden, vocab]. ``layer_types[i]``
names layer ``i``'s mixer and ``num_attention_heads_per_layer[i]`` its
query heads ``H`` (48 in ``full_attention`` layers, 72 in
``sliding_attention`` layers); every layer has ``KVH`` = 8 key and value
heads of ``D`` = 128, and query head ``j`` reads KV head
``j // (H / KVH)`` (groups of 6 and of 9)::

    q = W_q n -> [H, D];  k = W_k n -> [KVH, D];  v = W_v n -> [KVH, D]
    full:    rotate-half rotary on values 0..63 of q_j and k_i
             (partial_rotary_factor 0.5), inverse frequencies YaRN's:
             pair i of the 32 turns at f_i = 500000^(-2i / 64); with
             c(n) = 64 ln(8192 / (2 pi n)) / (2 ln 500000),
             low = floor(c(32)), high = ceil(c(1)),
             r_i = clip((i - low) / (high - low), 0, 1):
             inv_i = (f_i / 128) r_i + f_i (1 - r_i);
             cos and sin times attention_factor 1.4852030263919618
    sliding: rotate-half rotary on all 128 values, theta 10000, unscaled
    s[t, u] = q_j[t] . k_{j // G}[u] / sqrt(128),  u <= t
              (sliding: and t - 511 <= u: 512 rows, the token's own among
              them)
    o_j = softmax(s) v_{j // G};  g = sigmoid(W_g n) in R^H
    x <- x + W_o concat_j(g_j o_j)

*FFN*: layer 0 (``mlp_only_layers``) a gated SiLU at 12,288; the others
``p = softmax(W_r m)`` over all 256 experts in float32, the 10 largest,
weights ``2.5 p_e / sum p_top`` (``norm_topk_prob``,
``moe_routed_scaling_factor``), experts ``W_down (silu(W_gate m) * W_up
m)`` of width 1,024, plus one shared expert of 1,024 every token passes,
added ungated.

Assumed (the configuration's ``assumed`` says each again, with its
ground): a softmax router (the keys ``norm_topk_prob``,
``decoder_sparse_step``, ``mlp_only_layers`` are the Qwen-MoE family's,
whose router is a softmax; ``moe_router_logit_softcapping`` 0 = off); the
shared expert added without a gate of its own (the config has no key for
one); no q/k norm (no key); the head gate as ``sigmoid(W_g n)``, one
value a head, before ``W_o`` (``gating: per-head``; dots3's form);
``sliding_window`` 512 counting the token itself; YaRN's ramp truncated
to whole pairs (the transformers default).

Departures shared with the program: a configuration that holds a share
(``num_experts`` experts from ``share.first_expert`` of the ``published``
256; ``vocab_size`` ids from ``share.first_vocab``) leaves out what the
absent experts would add and embeds an id held elsewhere as zeros. The
program stores K and V in bfloat16; the reference stores nothing.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refmath as rm

FAMILY = "gqa_window_moe_decoder"
#: query rows a block of the attention (each against every key)
_QUERY_ROWS = 1024


def _sizes(cfg: dict) -> dict:
    pub, share = cfg.get("published", {}), cfg.get("share", {})
    layers = cfg["num_hidden_layers"]
    return dict(
        h=cfg["hidden_size"], v=cfg["vocab_size"], layers=layers,
        kinds=list(cfg["layer_types"][:layers]),
        heads=list(cfg["num_attention_heads_per_layer"][:layers]),
        kvh=cfg["num_key_value_heads"], d=cfg["head_dim"],
        rope=cfg["rope_parameters"], window=cfg["sliding_window"],
        e=pub.get("num_experts", cfg["num_experts"]),
        held=cfg["num_experts"], first=share.get("first_expert", 0),
        first_vocab=share.get("first_vocab", 0),
        k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        shared=cfg["shared_expert_intermediate_size"],
        scale=cfg["moe_routed_scaling_factor"],
        dense=set(cfg["mlp_only_layers"]), dense_f=cfg["intermediate_size"],
        eps=cfg["rms_norm_eps"])


def param_spec(cfg: dict) -> dict:
    z = _sizes(cfg)
    h, kd = z["h"], z["kvh"] * z["d"]
    spec = {"embed": ((z["v"], h), "normal"), "norm_f": ((h,), "scale"),
            "head": ((h, z["v"]), "glorot")}

    def gated(pre, width, *lead):
        return {f"{pre}/gate": ((*lead, h, width), "glorot"),
                f"{pre}/up": ((*lead, h, width), "glorot"),
                f"{pre}/down": ((*lead, width, h), "glorot")}

    for i, nh in enumerate(z["heads"]):
        pre = f"layers/{i}"
        spec.update({
            f"{pre}/attn_norm": ((h,), "scale"),
            f"{pre}/ffn_norm": ((h,), "scale"),
            f"{pre}/attn/wq": ((h, nh * z["d"]), "glorot"),
            f"{pre}/attn/wk": ((h, kd), "glorot"),
            f"{pre}/attn/wv": ((h, kd), "glorot"),
            f"{pre}/attn/wg": ((h, nh), "glorot"),
            f"{pre}/attn/wo": ((nh * z["d"], h), "glorot")})
        if i in z["dense"]:
            spec.update(gated(f"{pre}/mlp", z["dense_f"]))
        else:
            spec.update({f"{pre}/moe/router": ((h, z["e"]), "glorot"),
                         **gated(f"{pre}/moe", z["f"], z["held"]),
                         **gated(f"{pre}/moe/shared", z["shared"])})
    return spec


def rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def inverse_frequencies(rp: dict, head_dim: int) -> np.ndarray:
    """The inverse frequencies of one ``rope_parameters`` entry, [dim / 2]
    float32, ``dim`` the rotated part of a head."""
    dim = int(head_dim * rp.get("partial_rotary_factor", 1))
    base = float(rp["rope_theta"])
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type", "default") == "default":
        return freq.astype(np.float32)
    if rp["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rp['rope_type']!r}")
    original = rp["original_max_position_embeddings"]

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(pair_of(rp["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rp["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (freq / rp["factor"] * ramp + freq * (1.0 - ramp)).astype(
        np.float32)


def rope(x, pos, rp: dict):
    """Rotate-half rotary over the leading part of the last axis that
    ``rp`` rotates: ``x`` [S, D], ``pos`` [S]."""
    inv = jnp.asarray(inverse_frequencies(rp, x.shape[-1]))
    d = 2 * inv.shape[0]
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    mag = float(rp.get("attention_factor", 1.0))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1) * mag
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1) * mag
    xr = x[:, :d]
    turned = jnp.concatenate([-xr[:, d // 2:], xr[:, :d // 2]], axis=-1)
    return jnp.concatenate([xr * cos + turned * sin, x[:, d:]], axis=-1)


def _blocks(s: int) -> int:
    return _QUERY_ROWS if s % _QUERY_ROWS == 0 else s


def mixer(z, i, ap, n, precision):
    """[S, hidden] normed rows through layer ``i``'s attention, a query
    head at a time and a block of query rows at a time. A window layer's
    block of rows is computed against the ``2 x rows`` keys that end with
    its own."""
    s = n.shape[0]
    nh, kvh, d = z["heads"][i], z["kvh"], z["d"]
    kind = z["kinds"][i]
    rp = z["rope"][kind]
    window = z["window"] if kind == "sliding_attention" else 0
    pos = jnp.arange(s)
    k = rm.einsum("si,io->so", n, ap["wk"], precision).reshape(s, kvh, d)
    k = jnp.stack([rope(k[:, j], pos, rp) for j in range(kvh)])  # [KVH,S,D]
    v = rm.einsum("si,io->so", n, ap["wv"], precision).reshape(
        s, kvh, d).transpose(1, 0, 2)
    gate = jax.nn.sigmoid(rm.einsum("si,ih->sh", n, ap["wg"], precision))
    rows = _blocks(s)
    near = 0 < window <= rows and s > 2 * rows   # a band of key blocks

    def head(out, xs):
        wq, wo, gh, at = xs     # [hidden, D], [D, hidden], [S], KV head
        q = rope(rm.einsum("si,io->so", n, wq, precision), pos, rp)
        kh, vh = k[at], v[at]

        def queries(ys):
            qb, first = ys
            if near:
                lo = jnp.maximum(first - rows, 0)
                kb = jax.lax.dynamic_slice_in_dim(kh, lo, 2 * rows)
                vb = jax.lax.dynamic_slice_in_dim(vh, lo, 2 * rows)
                kp = lo + jnp.arange(2 * rows)
            else:
                kb, vb, kp = kh, vh, pos
            qp = first + jnp.arange(rows)
            ok = kp[None, :] <= qp[:, None]
            if window:
                ok &= kp[None, :] > qp[:, None] - window
            sc = rm.einsum("qd,kd->qk", qb, kb, precision) / math.sqrt(d)
            pr = jax.nn.softmax(jnp.where(ok, sc, -1e30), axis=-1)
            return rm.einsum("qk,kd->qd", pr, vb, precision)

        ctx = jax.lax.map(queries, (
            q.reshape(s // rows, rows, d),
            jnp.arange(0, s, rows))).reshape(s, d)
        return out + rm.einsum("sd,do->so", ctx * gh[:, None], wo,
                               precision), None

    out, _ = jax.lax.scan(head, jnp.zeros((s, z["h"]), jnp.float32), (
        ap["wq"].reshape(z["h"], nh, d).transpose(1, 0, 2),
        ap["wo"].reshape(nh, d, z["h"]), gate.T,
        jnp.arange(nh) // (nh // kvh)))
    return out


def _by_rows(fn, x):
    """``fn`` over blocks of rows (a 16 k-row float32 activation of the
    dense FFN is 0.8 GB)."""
    s = x.shape[0]
    rows = 4 * _QUERY_ROWS
    if s % rows or s == rows:
        return fn(x)
    return jax.lax.map(fn, x.reshape(s // rows, rows, -1)).reshape(s, -1)


def gated(p, x, precision):
    return rm.einsum(
        "sf,fo->so",
        jax.nn.silu(rm.einsum("si,if->sf", x, p["gate"], precision))
        * rm.einsum("si,if->sf", x, p["up"], precision), p["down"],
        precision)


def routed(z, mp, x, precision):
    """The held experts' part of the layer: every held expert over every
    row (upcast one at a time), weighted by the row's share of it (0 for
    most)."""
    sc = jax.nn.softmax(rm.einsum("si,ie->se", x, mp["router"], precision),
                        axis=-1)
    w, idx = jax.lax.top_k(sc, z["k"])
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
    x = x + mixer(z, i, lp["attn"], n, precision)
    m = rms(x, lp["ffn_norm"], z["eps"])
    if "mlp" in lp:
        return x + _by_rows(lambda r: gated(lp["mlp"], r, precision), m)
    return x + _by_rows(
        lambda r: routed(z, lp["moe"], r, precision) + gated(
            lp["moe"]["shared"], r, precision), m)


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

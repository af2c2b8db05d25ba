"""Plain reference of A.X-K1's language-model layers (huggingface
skt/A.X-K1, ``model_type: axk1``, 61 layers, hidden 7,168, ~519 B
parameters). float32 throughout at ``highest`` matmul precision; no
kernel, no cache, no chunk, no absorption, no batching: K and V expanded
from the latent for EVERY row, every row against every earlier row, the
router written out group by group, a dense loop over the held experts.
Long sequences are computed in blocks of query rows and the experts are
upcast one at a time, so that a 29,696-token request fits.

Pre-norm, no bias; ``x`` a token's residual, ``n = rms(x)`` (eps 1e-6)::

    h = x + Attn(n)        y = h + FFN(rms(h))

a final RMSNorm and an untied head [hidden, vocab].

*Attention*, all 61 layers alike (``rope`` is rotate-half rotary at
position ``t`` over the 64 rope values, at YaRN's frequencies)::

    c_q = rms(W_qa n)                      [1536]
    [q_nope_h ; q_pe_h] = W_qb c_q         64 heads x (128 + 64)
    q_pe_h <- rope(q_pe_h, t)
    [c_kv ; k_pe] = W_kva n                [512 + 64]
    c_kv <- rms(c_kv)
    k_pe <- rope(k_pe, t)                  one rotated key part a token,
                                           shared by the heads
    [k_nope_h ; v_h] = W_kvb c_kv          64 heads x (128 + 128)
    a_h = softmax_{s <= t}( s . (q_nope_h . k_nope_h,s + q_pe_h . k_pe_s) ) v_h
    x <- x + W_o concat_h a_h

YaRN (``rope_scaling``: factor 32, original 4,096, beta_fast 32,
beta_slow 1, ``mscale`` 1, ``mscale_all_dim`` 1; ``rope_theta`` 1e4):
pair ``i`` of the 32 keeps ``theta^(-2i/64)`` where it turns more than 32
times within 4,096 positions, that over 32 where it turns less than
once, a linear ramp between (pairs 10 to 23 here). ``mscale(f, m) = 0.1
m ln f + 1``; cos and sin are multiplied by ``mscale(32, mscale) /
mscale(32, mscale_all_dim)`` = 1, and the softmax scale is ``s =
192^-1/2 x mscale(32, mscale_all_dim)^2`` = ``192^-1/2 x 1.81326``.

*FFN*: layer 0 a gated SiLU at 18,432 (``first_k_dense_replace`` 1); the
others, ``m = rms(h)``::

    p  = sigmoid(W_r m)              [192], float32
    p' = p + b                       the bias picks and does not weigh
    the 192 experts lie in 8 groups of 24 (n_group); a group's score is
    the sum of its two largest p'; the 4 best groups are kept
    (topk_group); the 8 largest p' inside them are picked
    (num_experts_per_tok)
    w_e = 2.5 . p_e / sum_{picked} p     (norm_topk_prob, routed_scaling_factor)
    y = h + sum_e w_e E_e(m) + Shared(m)

each expert and the shared one (``n_shared_experts`` 1) a gated SiLU at
2,048: ``W_down (silu(W_gate m) * W_up m)``.

Assumed (the configuration's ``assumed`` says each again, with its
reason): the router's form (``topk_method`` reads ``"none"`` beside
``n_group`` 8 / ``topk_group`` 4 / sigmoid: read as DeepSeek-V3's
group-limited router; experts outside the kept groups cannot be picked,
where transformers zeroes their scores: the same picks while the kept
groups hold 8 experts of positive score, which 96 sigmoid scores always
do); ``e_score_correction_bias`` drawn N(0, 0.02); rotate-half rotary
where the published code interleaves pairs (a relabelling of ``W_qb``'s
and ``W_kva``'s columns under seeded weights); ``seq_aux`` and
``ep_size`` say nothing of the forward.

Departures shared with the program: a configuration that holds a share
(``n_routed_experts`` experts from ``share.first_expert`` of the
``published`` 192; ``vocab_size`` ids from ``share.first_vocab``) leaves
out what the absent experts would add and embeds an id held elsewhere as
zeros. The program stores latent rows in bfloat16, 640 values a row
(576 and zeros), expands K and V a tile of rows at a time in a chunk and
absorbs ``W_kvb`` into the query in a step; the reference stores nothing
and absorbs nothing.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refmath as rm

FAMILY = "dense_mla_group_routed_moe_decoder"
#: query rows a block of the attention (each against every key)
_QUERY_ROWS = 1024


def _sizes(cfg: dict) -> dict:
    pub, share = cfg.get("published", {}), cfg.get("share", {})
    e = pub.get("n_routed_experts", cfg["n_routed_experts"])
    return dict(
        h=cfg["hidden_size"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], nh=cfg["num_attention_heads"],
        qr=cfg["q_lora_rank"], rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], pe=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], e=e, held=cfg["n_routed_experts"],
        first=share.get("first_expert", 0),
        first_vocab=share.get("first_vocab", 0),
        k=cfg["num_experts_per_tok"], groups=cfg["n_group"],
        top_groups=cfg["topk_group"], f=cfg["moe_intermediate_size"],
        shared=cfg["n_shared_experts"], scale=cfg["routed_scaling_factor"],
        dense=cfg["first_k_dense_replace"], dense_f=cfg["intermediate_size"],
        eps=cfg["rms_norm_eps"])


def param_spec(cfg: dict) -> dict:
    z = _sizes(cfg)
    h = z["h"]
    spec = {"embed": ((z["v"], h), "normal"), "norm_f": ((h,), "scale"),
            "head": ((h, z["v"]), "glorot")}

    def gated(pre, width, *lead):
        return {f"{pre}/gate": ((*lead, h, width), "glorot"),
                f"{pre}/up": ((*lead, h, width), "glorot"),
                f"{pre}/down": ((*lead, width, h), "glorot")}

    for i in range(z["layers"]):
        pre = f"layers/{i}"
        spec.update({
            f"{pre}/attn_norm": ((h,), "scale"),
            f"{pre}/ffn_norm": ((h,), "scale"),
            f"{pre}/mla/wqa": ((h, z["qr"]), "glorot"),
            f"{pre}/mla/q_norm": ((z["qr"],), "scale"),
            f"{pre}/mla/wqb": ((z["qr"], z["nh"] * (z["nope"] + z["pe"])),
                               "glorot"),
            f"{pre}/mla/wkva": ((h, z["rank"] + z["pe"]), "glorot"),
            f"{pre}/mla/kv_norm": ((z["rank"],), "scale"),
            f"{pre}/mla/wkvb": ((z["rank"],
                                 z["nh"] * (z["nope"] + z["vd"])), "glorot"),
            f"{pre}/mla/wo": ((z["nh"] * z["vd"], h), "glorot")})
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


def mscale(factor: float, m: float) -> float:
    """YaRN's magnitude correction: ``0.1 m ln(factor) + 1`` (1 where the
    context is not stretched)."""
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def inverse_frequencies(cfg: dict) -> np.ndarray:
    """The inverse frequencies of the ``qk_rope_head_dim`` rope values,
    [pe / 2] float32: ``rope_theta``'s own, blended with those over
    ``factor`` by YaRN's ramp where ``rope_scaling`` is given."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return freq.astype(np.float32)
    if rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError(f"unknown rope_scaling {rs!r}")
    original = rs["original_max_position_embeddings"]

    def pair_of(turns):     # the pair that turns `turns` times in `original`
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (freq / rs["factor"] * ramp + freq * (1.0 - ramp)).astype(
        np.float32)


def rope_magnitude(cfg: dict) -> float:
    """What multiplies cos and sin: ``mscale(f, mscale) / mscale(f,
    mscale_all_dim)``."""
    rs = cfg.get("rope_scaling")
    if not rs:
        return 1.0
    return (mscale(rs["factor"], rs.get("mscale", 1.0))
            / mscale(rs["factor"], rs.get("mscale_all_dim", 0.0)))


def softmax_scale(cfg: dict) -> float:
    """``(nope + pe)^-1/2 x mscale(f, mscale_all_dim)^2``."""
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim", 0.0):
        s *= mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def rope(x, pos, inv, magnitude: float = 1.0):
    """Rotate-half rotary over the last axis: ``x`` [S, D], ``pos`` [S],
    ``inv`` [D / 2] inverse frequencies."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1) * magnitude
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1) * magnitude
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _blocks(s: int) -> int:
    return _QUERY_ROWS if s % _QUERY_ROWS == 0 else s


def attention(cfg, z, mp, n, precision):
    """[S, hidden] normed rows through one attention layer, a head at a
    time (its q, K and V made from the two latents inside the loop) and
    a block of query rows at a time, each against every earlier row."""
    s = n.shape[0]
    nh, nope, pe, vd, rank = z["nh"], z["nope"], z["pe"], z["vd"], z["rank"]
    pos = jnp.arange(s)
    inv, mag = inverse_frequencies(cfg), rope_magnitude(cfg)
    scale = softmax_scale(cfg)
    c_q = rms(rm.einsum("si,io->so", n, mp["wqa"], precision),
              mp["q_norm"], z["eps"])
    c = rm.einsum("si,io->so", n, mp["wkva"], precision)
    c_kv = rms(c[:, :rank], mp["kv_norm"], z["eps"])
    k_pe = rope(c[:, rank:], pos, inv, mag)
    rows = _blocks(s)

    def head(out, xs):
        wq, wkv, wo = xs        # [qr, nope+pe], [rank, nope+vd], [vd, h]
        q = rm.einsum("sr,ro->so", c_q, wq, precision)
        q_pe = rope(q[:, nope:], pos, inv, mag)
        kv = rm.einsum("sc,co->so", c_kv, wkv, precision)

        def queries(ys):
            qn, qp, first = ys
            sc = (rm.einsum("qd,kd->qk", qn, kv[:, :nope], precision)
                  + rm.einsum("qd,kd->qk", qp, k_pe, precision)) * scale
            ok = pos[None, :] <= (first + jnp.arange(rows))[:, None]
            pr = jax.nn.softmax(jnp.where(ok, sc, -1e30), axis=-1)
            return rm.einsum("qk,kd->qd", pr, kv[:, nope:], precision)

        ctx = jax.lax.map(queries, (
            q[:, :nope].reshape(s // rows, rows, -1),
            q_pe.reshape(s // rows, rows, -1),
            jnp.arange(0, s, rows))).reshape(s, vd)
        return out + rm.einsum("sv,vo->so", ctx, wo, precision), None

    out, _ = jax.lax.scan(head, jnp.zeros((s, z["h"]), jnp.float32), (
        mp["wqb"].reshape(z["qr"], nh, nope + pe).transpose(1, 0, 2),
        mp["wkvb"].reshape(rank, nh, nope + vd).transpose(1, 0, 2),
        mp["wo"].reshape(nh, vd, z["h"])))
    return out


def _by_rows(fn, x):
    """``fn`` over blocks of rows (a 29 k-row float32 activation of the
    dense FFN is 2.2 GB)."""
    s = x.shape[0]
    rows = 2 * _QUERY_ROWS
    if s % rows or s == rows:
        return fn(x)
    return jax.lax.map(fn, x.reshape(s // rows, rows, -1)).reshape(s, -1)


def gated(p, x, precision):
    return rm.einsum(
        "sf,fo->so",
        jax.nn.silu(rm.einsum("si,if->sf", x, p["gate"], precision))
        * rm.einsum("si,if->sf", x, p["up"], precision), p["down"],
        precision)


def pick(z, mp, x, precision):
    """The router's choice, group by group: ``(idx [S, k], w [S, k])``
    over all ``e`` published experts."""
    p = jax.nn.sigmoid(rm.einsum("si,ie->se", x, mp["router"], precision))
    biased = p + mp["router_bias"].astype(jnp.float32)
    g, size = z["groups"], z["e"] // z["groups"]
    # a group's score: the sum of its two largest biased scores
    score = jnp.stack([
        jnp.sum(jax.lax.top_k(biased[:, j * size:(j + 1) * size], 2)[0],
                axis=-1) for j in range(g)], axis=1)        # [S, G]
    _, kept = jax.lax.top_k(score, z["top_groups"])
    in_kept = jnp.any(kept[:, :, None] == jnp.arange(g)[None, None, :],
                      axis=1)                               # [S, G]
    allowed = jnp.repeat(in_kept, size, axis=1)             # [S, e]
    _, idx = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf), z["k"])
    w = jnp.take_along_axis(p, idx, axis=-1)
    return idx, z["scale"] * w / jnp.sum(w, axis=-1, keepdims=True)


def routed(z, mp, x, precision):
    """The held experts' part of the layer: every held expert over every
    row (upcast one at a time), weighted by the row's share of it (0 for
    most)."""
    idx, w = pick(z, mp, x, precision)
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


def layer(cfg, z, lp, x, precision):
    n = rms(x, lp["attn_norm"], z["eps"])
    x = x + attention(cfg, z, lp["mla"], n, precision)
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
            x = layer(cfg, z, params["layers"][str(i)], x, precision)
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

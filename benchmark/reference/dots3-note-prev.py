"""Plain reference of dots3-note-prev's language-model layers (huggingface
dots-studio/dots3-note-prev, ``model_type: dots3_note``, 46 layers, hidden
5,120). float32 throughout at ``highest`` matmul precision; no kernel, no
cache, no chunk, no batching: every row against every earlier row, the
selection a plain ``top_k`` over a row's index scores, a dense loop over
the held experts. Long sequences are computed in blocks of query rows and
the experts are upcast one at a time, so that a 32 k-token request fits.

Pre-norm, no bias; ``x`` a token's residual, ``n = rms(x)`` (eps 1e-5)::

    h = x + Mixer(n)        y = h + FFN(rms(h))

a final RMSNorm and an untied head [hidden, vocab]. ``layer_types[i]``
names layer ``i``'s mixer; ``rope`` is rotate-half rotary at position
``t``; ``r_q = sqrt(hidden / q_lora_rank)``, ``r_kv = sqrt(hidden /
kv_lora_rank)`` (``apply_mla_qkv_lora_rescale``).

*Full-attention layers* (``full_attention``: 0, 1, 5, 9, ...)::

    c_q = rms(W_qa n) . r_q            [1024]     r_q = sqrt(5120 / 1024)
    [q_nope_h ; q_pe_h] = W_qb c_q     128 heads x (128 + 64)
    q_pe_h <- rope(q_pe_h, t, 8e7)
    [c_kv ; k_pe] = W_kva n            [512 + 64]
    c_kv <- rms(c_kv) . r_kv           r_kv = sqrt(5120 / 512)
    k_pe <- rope(k_pe, t, 8e7)         one rotated key part a token,
                                       shared by the heads
    [k_nope_h ; v_h] = W_kvb c_kv      128 heads x (128 + 128)
    indexer:  qI_j = rope64(W_Iq c_q)_j   64 heads x 128, the first 64
                                          values of each rotated
              kI   = rope64(LN(W_Ik n))   [128], one a token
              w    = W_Iw n . 64^-1/2 . 128^-1/2           [64]
              I[t, s] = sum_j w_tj . relu(qI_tj . kI_s),   s <= t
              S_t = the 2,048 largest of I[t, :t]  (every s <= t while
                    t < 2,048; equal scores: the lower s first)
    a_h = softmax_{s in S_t}( (q_nope_h . k_nope_h,s + q_pe_h . k_pe_s)
                              / sqrt(192) ) . v_h
    g = sigmoid(W_g n)  [128];   x <- x + W_o concat_h(g_h . a_h)

*Window layers* (``sliding_attention``): the same without indexer, from
the ``swa_*`` keys: 64 heads, ``q_lora`` 1,024, ``kv_lora`` 1,024
(``r = sqrt(5)`` both), nope 192, rope 64 (theta 50,000), v 128, scale
``256^-1/2``, gate [64], and ``s`` runs over ``t - 513 < s <= t`` (513
rows, the token's own among them).

*FFN*: layer 0 a gated SiLU at 13,824; the others ``p = sigmoid(W_r m)``
over all 256 experts, the 8 largest of ``p + b`` (the bias selects only,
one group), weights ``p_e / sum p_top`` x 1.0, experts ``W_down
(silu(W_gate m) * W_up m)`` of width 1,536, plus one shared expert of
1,536 every token passes.

Assumed (the configuration's ``assumed`` says each again, with its
origin): the indexer's inner form, LayerNorm (scale and bias) on ``kI``,
rotate-half rotary on the leading 64 index values and ``w``'s two
constants are DeepSeek-V3.2-Exp's published indexer, whose keys the
config uses (its Hadamard rotation is left out: it changes no dot
product; its fp8 too: a precision); the gate as ``sigmoid(W_g n)``, one
value a head, before ``W_o`` (``attention_gate_type: headwise``); the
rescale as ``sqrt(hidden / rank)`` on each normed latent where it enters
``W_qb`` / ``W_kvb`` / ``W_Iq``; ``n_group = topk_group = 1``; the
window's 513 counting the token itself. Not built: the vision and audio
towers and the multi-token-prediction module (outside the language
model's config).

Departures shared with the program: a configuration that holds a share
(``n_routed_experts`` experts from ``share.first_expert`` of the
``published`` 256; ``vocab_size`` ids from ``share.first_vocab``) leaves
out what the absent experts would add and embeds an id held elsewhere as
zeros. The program stores latent rows and index keys in bfloat16, padded
to whole lane tiles; the reference stores nothing.
"""

import math

import jax
import jax.numpy as jnp

from benchmark import refmath as rm

FAMILY = "sparse_mla_window_moe_decoder"
#: query rows a block of the attention (each against every key)
_QUERY_ROWS = 1024


def _sizes(cfg: dict) -> dict:
    pub, share = cfg.get("published", {}), cfg.get("share", {})
    layers = cfg["num_hidden_layers"]
    full = dict(nh=cfg["num_attention_heads"], qr=cfg["q_lora_rank"],
                rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
                pe=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
                theta=cfg["rope_theta"])
    win = dict(nh=cfg["swa_num_attention_heads"], qr=cfg["swa_q_lora_rank"],
               rank=cfg["swa_kv_lora_rank"],
               nope=cfg["swa_qk_nope_head_dim"],
               pe=cfg["swa_qk_rope_head_dim"], vd=cfg["swa_v_head_dim"],
               theta=cfg["swa_rope_theta"])
    return dict(
        h=cfg["hidden_size"], v=cfg["vocab_size"], layers=layers,
        kinds=list(cfg["layer_types"][:layers]), full=full, win=win,
        ij=cfg["index_n_heads"], idim=cfg["index_head_dim"],
        topk=cfg["index_topk"], window=cfg["sliding_window_size"],
        e=pub.get("n_routed_experts", cfg["n_routed_experts"]),
        held=cfg["n_routed_experts"], first=share.get("first_expert", 0),
        first_vocab=share.get("first_vocab", 0),
        k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        shared=cfg["n_shared_experts"],
        scale=cfg["routed_scaling_factor"],
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

    for i, kind in enumerate(z["kinds"]):
        pre = f"layers/{i}"
        g = z["full"] if kind == "full_attention" else z["win"]
        spec.update({
            f"{pre}/attn_norm": ((h,), "scale"),
            f"{pre}/ffn_norm": ((h,), "scale"),
            f"{pre}/mla/wqa": ((h, g["qr"]), "glorot"),
            f"{pre}/mla/q_norm": ((g["qr"],), "scale"),
            f"{pre}/mla/wqb": ((g["qr"], g["nh"] * (g["nope"] + g["pe"])),
                               "glorot"),
            f"{pre}/mla/wkva": ((h, g["rank"] + g["pe"]), "glorot"),
            f"{pre}/mla/kv_norm": ((g["rank"],), "scale"),
            f"{pre}/mla/wkvb": ((g["rank"],
                                 g["nh"] * (g["nope"] + g["vd"])), "glorot"),
            f"{pre}/mla/wg": ((h, g["nh"]), "glorot"),
            f"{pre}/mla/wo": ((g["nh"] * g["vd"], h), "glorot")})
        if kind == "full_attention":
            spec.update({
                f"{pre}/mla/index/wq": ((g["qr"], z["ij"] * z["idim"]),
                                        "glorot"),
                f"{pre}/mla/index/wk": ((h, z["idim"]), "glorot"),
                f"{pre}/mla/index/k_scale": ((z["idim"],), "scale"),
                f"{pre}/mla/index/k_bias": ((z["idim"],), "bias"),
                f"{pre}/mla/index/ww": ((h, z["ij"]), "glorot")})
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


def rope(x, pos, theta):
    """Rotate-half rotary over the last axis: ``x`` [S, ..., D], ``pos``
    [S]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), d // 2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _blocks(s: int) -> int:
    return _QUERY_ROWS if s % _QUERY_ROWS == 0 else s


def index_keys(z, ip, n, precision):
    """kI of every token: [S, D]."""
    pe = z["full"]["pe"]
    k = rm.einsum("si,io->so", n, ip["wk"], precision)
    mu = jnp.mean(k, axis=-1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(
        jnp.mean(jnp.square(k - mu), axis=-1, keepdims=True) + z["eps"]) \
        * ip["k_scale"].astype(jnp.float32) \
        + ip["k_bias"].astype(jnp.float32)
    return jnp.concatenate(
        [rope(k[:, :pe], jnp.arange(k.shape[0]), z["full"]["theta"]),
         k[:, pe:]], axis=-1)


def selected(z, ip, n, c_q, precision):
    """[S, S] bool: the keys each row of a full-attention layer attends
    to. A block of query rows at a time: its index scores I[t, s] a head
    at a time (``-inf`` where s > t), then a plain ``top_k`` (every
    candidate where a row has fewer than ``index_topk``)."""
    s = n.shape[0]
    j, d, pe = z["ij"], z["idim"], z["full"]["pe"]
    pos = jnp.arange(s)
    rows = _blocks(s)
    keys = index_keys(z, ip, n, precision)
    q = rm.einsum("sr,ro->so", c_q, ip["wq"], precision).reshape(s, j, d)
    q = jnp.concatenate([rope(q[..., :pe], pos, z["full"]["theta"]),
                         q[..., pe:]], axis=-1)
    w = rm.einsum("si,ij->sj", n, ip["ww"], precision) \
        * (j ** -0.5 * d ** -0.5)
    k = min(z["topk"], s)

    def block(xs):
        qb, wb, pb = xs             # [J, rows, D], [J, rows], [rows]

        def head(sc, ys):
            qh, wh = ys
            return sc + wh[:, None] * jax.nn.relu(
                rm.einsum("qd,kd->qk", qh, keys, precision)), None

        sc, _ = jax.lax.scan(head, jnp.zeros((rows, s), jnp.float32),
                             (qb, wb))
        sc = jnp.where(pos[None, :] <= pb[:, None], sc, -jnp.inf)
        vals, idx = jax.lax.top_k(sc, k)
        return jnp.zeros((rows, s), bool).at[
            jnp.arange(rows)[:, None], idx].set(vals > -jnp.inf)

    return jax.lax.map(block, (
        q.reshape(s // rows, rows, j, d).transpose(0, 2, 1, 3),
        w.reshape(s // rows, rows, j).transpose(0, 2, 1),
        pos.reshape(s // rows, rows))).reshape(s, s)


def mla(z, g, mp, n, allowed_of, precision, reach: int = 0):
    """[S, hidden] normed rows through an MLA mixer of geometry ``g``, a
    head at a time (its q, K and V made from the two latents inside the
    loop) and a block of query rows at a time; ``allowed_of(c_q) -> [S, S]
    bool`` says which keys each row attends to. ``reach`` > 0: no row
    sees further back than that, so a block of rows is computed against
    the ``2 x rows`` keys that end with its own."""
    s = n.shape[0]
    nh, nope, pe, vd, rank = g["nh"], g["nope"], g["pe"], g["vd"], g["rank"]
    pos = jnp.arange(s)
    c_q = rms(rm.einsum("si,io->so", n, mp["wqa"], precision),
              mp["q_norm"], z["eps"]) * math.sqrt(z["h"] / g["qr"])
    c = rm.einsum("si,io->so", n, mp["wkva"], precision)
    c_kv = rms(c[:, :rank], mp["kv_norm"], z["eps"]) \
        * math.sqrt(z["h"] / rank)
    k_pe = rope(c[:, rank:], pos, g["theta"])
    gate = jax.nn.sigmoid(rm.einsum("si,ih->sh", n, mp["wg"], precision))
    allowed = allowed_of(c_q)
    rows = _blocks(s)
    near = 0 < reach <= rows and s > 2 * rows   # a band of key blocks

    def head(out, xs):
        wq, wkv, wo, gh = xs    # [qr, nope+pe], [rank, nope+vd], [vd, h], [S]
        q = rm.einsum("sr,ro->so", c_q, wq, precision)
        q_pe = rope(q[:, nope:], pos, g["theta"])
        kv = rm.einsum("sc,co->so", c_kv, wkv, precision)

        def queries(ys):
            qn, qp, ok, first = ys
            if near:
                lo = jnp.maximum(first - rows, 0)
                kvb = jax.lax.dynamic_slice_in_dim(kv, lo, 2 * rows)
                kpb = jax.lax.dynamic_slice_in_dim(k_pe, lo, 2 * rows)
                ok = jax.lax.dynamic_slice_in_dim(ok, lo, 2 * rows, axis=1)
            else:
                kvb, kpb = kv, k_pe
            sc = (rm.einsum("qd,kd->qk", qn, kvb[:, :nope], precision)
                  + rm.einsum("qd,kd->qk", qp, kpb, precision)
                  ) / math.sqrt(nope + pe)
            pr = jax.nn.softmax(jnp.where(ok, sc, -1e30), axis=-1)
            return rm.einsum("qk,kd->qd", pr, kvb[:, nope:], precision)

        ctx = jax.lax.map(queries, (
            q[:, :nope].reshape(s // rows, rows, -1),
            q_pe.reshape(s // rows, rows, -1),
            allowed.reshape(s // rows, rows, s),
            jnp.arange(0, s, rows))).reshape(s, vd)
        return out + rm.einsum("sv,vo->so", ctx * gh[:, None], wo,
                               precision), None

    out, _ = jax.lax.scan(head, jnp.zeros((s, z["h"]), jnp.float32), (
        mp["wqb"].reshape(g["qr"], nh, nope + pe).transpose(1, 0, 2),
        mp["wkvb"].reshape(rank, nh, nope + vd).transpose(1, 0, 2),
        mp["wo"].reshape(nh, vd, z["h"]), gate.T))
    return out


def mixer(z, kind, mp, n, precision):
    s = n.shape[0]
    pos = jnp.arange(s)
    if kind == "full_attention":
        return mla(z, z["full"], mp, n,
                   lambda c_q: selected(z, mp["index"], n, c_q, precision),
                   precision)
    band = (pos[None, :] <= pos[:, None]) & (
        pos[None, :] > pos[:, None] - z["window"])
    return mla(z, z["win"], mp, n, lambda _: band, precision,
               reach=z["window"] - 1)


def _by_rows(fn, x):
    """``fn`` over blocks of rows (a 32 k-row float32 activation of the
    dense FFN is 1.8 GB)."""
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
    x = x + mixer(z, z["kinds"][i], lp["mla"], n, precision)
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


def selection(cfg: dict, params, tokens, layer_index: int):
    """[S, S] bool: the rows full-attention layer ``layer_index`` lets
    each row attend to (tests compare the program's selected set)."""
    z = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        x = embed(cfg, params, tokens)
        for i in range(layer_index):
            x = layer(z, i, params["layers"][str(i)], x, "f32")
        lp = params["layers"][str(layer_index)]
        n = rms(x, lp["attn_norm"], z["eps"])
        mp = lp["mla"]
        c_q = rms(rm.einsum("si,io->so", n, mp["wqa"], "f32"),
                  mp["q_norm"], z["eps"]) * math.sqrt(
                      z["h"] / z["full"]["qr"])
        return selected(z, mp["index"], n, c_q, "f32")

"""Kimi Delta Attention (arXiv:2510.26692): a delta-rule linear attention
whose state decays per channel.

Per head, with ``q, k`` in R^dk (L2-normalised, ``q`` scaled), ``v`` in
R^dv, a decay ``a`` in (0, 1)^dk and a rate ``b`` in (0, 1), the state
``S`` in R^{dk x dv} (float32, zero at a request's first token) moves as::

    S' = Diag(a_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Three computations of it, the same numbers:

- :func:`kda_recurrent`: the recurrence a token at a time (``lax.scan``):
  the oracle of the two below;
- :func:`kda_step`: one token of a batch of requests, each with its own
  state: the served decode step. The state is read twice and written
  once, not thrice read: ``S'^T k`` and ``S'^T q`` are one product over
  ``S`` (``a`` folded into ``k`` and ``q``), and ``o = S'^T q + b (k.q) r``
  with ``r = v - S'^T k`` needs no pass over the new state;
- :func:`kda_chunk_scan`: a prompt chunk of ``T`` tokens from a state
  carried in to the state carried out, in sub-chunks of ``chunk`` tokens:
  inside a sub-chunk the tokens' dependence on one another is one unit
  lower-triangular solve (the WY form of the delta rule), across
  sub-chunks the state is carried by a scan of ``T / chunk`` steps
  (``SUB_CHUNK`` tokens each: the chip's reading).

The decay is handled in logarithms. With ``g_t = sum_{i <= t} log a_i``
inside a sub-chunk, a pair (t, i <= t) is weighted ``exp(g_t - g_i)``
per channel, computed for the pair itself: ``exp(g_t) * exp(-g_i)``
overflows float32 once a channel has decayed by e^-88, which a channel
at the fast end of the family's range does inside 64 tokens. A token
that is no token (a chunk's padding) has ``log a = 0`` and ``b = 0`` and
leaves the state as it was.

:func:`causal_conv` is the short depthwise convolution over time in front
of ``q``, ``k`` and ``v``, with the ``K - 1`` inputs before the chunk
carried in.

Everything here is float32; the products are small (``dk = dv = 128``)
and run at ``highest`` precision, which on the chip is the state's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
#: tokens a sub-chunk of :func:`kda_chunk_scan`. On the chip, one layer's
#: scan over 1,024 tokens of 32 heads x 128 took 1.75 ms at 16, 2.19 at
#: 32, 3.45 at 64 and 6.18 at 128 (the pairwise decay and the solve grow
#: with the sub-chunk, the scan's steps are cheap), the same numbers to
#: 1e-8 (benchmark/records/pr32/call3/checks.log)
SUB_CHUNK = 16


def causal_conv(x: jax.Array, tail: jax.Array, w: jax.Array,
                n_valid=None) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution over time.

    ``x`` [T, C] the chunk's inputs, ``tail`` [K - 1, C] the inputs just
    before it (zeros at a request's first token), ``w`` [K, C] with
    ``w[K - 1]`` on the current token. Returns ``y`` [T, C] and the tail
    the next chunk starts from: the last ``K - 1`` inputs up to
    ``n_valid`` tokens of this one (default: all ``T``)."""
    t = x.shape[0]
    k = w.shape[0]
    xx = jnp.concatenate([tail.astype(jnp.float32),
                          x.astype(jnp.float32)], axis=0)   # [K-1+T, C]
    y = sum(w[j].astype(jnp.float32) * xx[j:j + t] for j in range(k))
    n = t if n_valid is None else n_valid
    return y, lax.dynamic_slice_in_dim(xx, n, k - 1, axis=0)


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_recurrent(s0, q, k, v, log_a, b):
    """The recurrence, token by token. ``s0`` [H, dk, dv]; ``q, k, log_a``
    [T, H, dk]; ``v`` [T, H, dv]; ``b`` [T, H]. Returns ``o`` [T, H, dv]
    and the last state."""
    def one(s, xs):
        qt, kt, vt, gt, bt = xs
        s = jnp.exp(gt)[..., None] * s
        r = vt - jnp.einsum("hkv,hk->hv", s, kt, precision=_HI)
        s = s + (bt[:, None] * kt)[..., None] * r[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=_HI)

    f32 = [x.astype(jnp.float32) for x in (q, k, v, log_a, b)]
    s, o = lax.scan(one, s0.astype(jnp.float32), tuple(f32))
    return o, s


def kda_step(s, q, k, v, a, b):
    """One token of each of ``B`` requests. ``s`` [B, H, dk, dv]; ``q, k,
    a`` [B, H, dk] (``a`` the decay itself, in (0, 1]); ``v`` [B, H, dv];
    ``b`` [B, H]. A row with ``a = 1`` and ``b = 0`` keeps its state.
    Returns ``o`` [B, H, dv] and the new state."""
    with jax.named_scope("kda_step"):
        kq = jnp.stack([a * k, a * q], axis=2)              # [B, H, 2, dk]
        proj = jnp.einsum("bhkv,bhjk->bhjv", s, kq, precision=_HI)
        r = v - proj[:, :, 0]
        bk = b[..., None] * k
        s = a[..., None] * s + bk[..., None] * r[:, :, None, :]
        kdotq = jnp.sum(bk * q, axis=-1, keepdims=True)
        return proj[:, :, 1] + kdotq * r, s


def kda_chunk_scan(s0, q, k, v, log_a, b, *, chunk: int = SUB_CHUNK):
    """``T`` tokens from the state ``s0`` [H, dk, dv] to the state after
    them, ``T`` a multiple of ``chunk``. Shapes as :func:`kda_recurrent`."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    if t % chunk:
        raise ValueError(f"{t} tokens are not whole sub-chunks of {chunk}")
    n = t // chunk

    def split(x):                       # [T, H, d] -> [N, H, c, d]
        return x.astype(jnp.float32).reshape(n, chunk, h, -1).transpose(
            0, 2, 1, 3)

    with jax.named_scope("kda_chunk_scan"):
        q, k, v, la = split(q), split(k), split(v), split(log_a)
        beta = b.astype(jnp.float32).reshape(n, chunk, h).transpose(0, 2, 1)
        g = jnp.cumsum(la, axis=2)                          # [N, H, c, dk]
        pos = jnp.arange(chunk)
        upto = pos[:, None] >= pos[None, :]                 # i <= t
        # exp(g_t - g_i) for the pair itself, 0 above the diagonal
        decay = jnp.exp(jnp.where(
            upto[:, :, None], g[:, :, :, None, :] - g[:, :, None, :, :],
            -jnp.inf))                                      # [N,H,c,c,dk]
        kk = jnp.sum(k[:, :, :, None, :] * k[:, :, None, :, :] * decay,
                     axis=-1)
        qk = jnp.sum(q[:, :, :, None, :] * k[:, :, None, :, :] * decay,
                     axis=-1)                               # [N, H, c, c]
        strict = pos[:, None] > pos[None, :]
        m = jnp.where(strict, kk * beta[:, :, None, :], 0.0) + jnp.eye(chunk)
        k_in = k * jnp.exp(g)               # what the carried state meets
        x = jax.scipy.linalg.solve_triangular(
            m, jnp.concatenate([v, k_in], axis=-1), lower=True,
            unit_diagonal=True)
        u0, w = x[..., :dv], x[..., dv:]    # u = u0 - w S
        q_in = q * jnp.exp(g)
        qkb = qk * beta[:, :, None, :]
        g_last = g[:, :, -1:, :]                            # [N, H, 1, dk]
        k_out = k * jnp.exp(g_last - g) * beta[..., None]
        keep = jnp.exp(g_last[:, :, 0, :])                  # [N, H, dk]

        def one(s, xs):
            u0_, w_, q_in_, qkb_, k_out_, keep_ = xs
            u = u0_ - jnp.einsum("hck,hkv->hcv", w_, s, precision=_HI)
            o = (jnp.einsum("hck,hkv->hcv", q_in_, s, precision=_HI)
                 + jnp.einsum("hci,hiv->hcv", qkb_, u, precision=_HI))
            s = keep_[..., None] * s + jnp.einsum(
                "hck,hcv->hkv", k_out_, u, precision=_HI)
            return s, o

        s, o = lax.scan(one, s0.astype(jnp.float32),
                        (u0, w, q_in, qkb, k_out, keep))
        return o.transpose(0, 2, 1, 3).reshape(t, h, dv), s

"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory with
exponential gating), after arXiv:2405.04517.

Both are recurrent over time. Projections (q/k/v/gates) are computed for the
whole sequence up front (MXU einsums); the per-step recurrence runs in a
``jax.lax.scan`` carrying the (stabilized, log-space) cell state — the TPU
adaptation of the paper's fused CUDA cell: sequential dependency in a scan,
everything parallelizable hoisted out of it. Decode is the same body at
S=1 with the state held in the serve cache.

mLSTM state per head: C (dk, dv), n (dk,), m ().   sLSTM state per head and
cell: c, n, m, h.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.runtime import partitioning as P


# ------------------------------------------------------------------ mLSTM --
def mlstm_dims(cfg):
    d_inner = 2 * cfg.d_model
    heads = cfg.lstm_heads
    return d_inner, heads, d_inner // heads


def mlstm_init(key, cfg):
    d_inner, heads, _ = mlstm_dims(cfg)
    ks = jax.random.split(key, 7)
    return {
        "up": layers.dense_init(ks[0], cfg.d_model, 2 * d_inner),
        "q": layers.dense_init(ks[1], d_inner, d_inner),
        "k": layers.dense_init(ks[2], d_inner, d_inner),
        "v": layers.dense_init(ks[3], d_inner, d_inner),
        "igate": layers.dense_init(ks[4], d_inner, heads, scale=0.01),
        "fgate": {"w": jax.random.normal(ks[5], (d_inner, heads),
                                         jnp.float32) * 0.01,
                  "b": jnp.full((heads,), 3.0, jnp.float32)},
        "down": layers.dense_init(ks[6], d_inner, cfg.d_model),
    }


MLSTM_CHUNK = 256


def _mlstm_chunked(q, k, v, i_pre, f_pre, state, chunk: int = MLSTM_CHUNK):
    """Chunkwise-parallel mLSTM — exact, max-stabilized (GLA-style).

    The per-token scan carries an O(dh^2) matrix state whose HBM traffic is
    S * B * H * dh^2 — the dominant roofline term for xlstm at train_4k.
    This form carries state only BETWEEN chunks (S/chunk times) and computes
    the intra-chunk part as a causal, decay-weighted attention contraction
    on the MXU. Derivation: with F_t = cumsum(log f), a_j = log i_j - F_j,
    g_t = max(m_in, cummax(a)_t), m_t = F_t + g_t:

      w_tj  = exp(a_j - g_t)               (intra weights, j <= t)
      u_t   = exp(m_in - g_t)              (carry-in weight)
      h_t   = num_t / max(|den_t|, 1)
      num_t = u_t (q_t . Chat_in) + sum_j w_tj (q_t . k_j) v_j
      den_t = u_t (q_t . nhat_in) + sum_j w_tj (q_t . k_j)

    which reproduces the sequential recurrence exactly (same stabilizer).
    q/k/v: (B, S, H, D); i_pre/f_pre: (B, S, H); state: (Chat, nhat, m).
    """
    b, s, h, d = q.shape
    pad = (-s) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        i_pre = jnp.pad(i_pre, ((0, 0), (0, pad), (0, 0)),
                        constant_values=-1e30)   # padded i gate ~ 0
        f_pre = jnp.pad(f_pre, ((0, 0), (0, pad), (0, 0)),
                        constant_values=30.0)    # padded f gate ~ 1
    nc = q.shape[1] // chunk

    def resh(t):
        return t.reshape((b, nc, chunk) + t.shape[2:]).swapaxes(0, 1)

    q_c, k_c, v_c, i_c, f_c = map(resh, (q, k, v, i_pre, f_pre))

    def chunk_body(carry, inp):
        chat, nhat, m_in = carry                 # (B,H,D,D),(B,H,D),(B,H)
        qc, kc, vc, ic, fc = inp                 # (B,L,H,*) / (B,L,H)
        qc32, kc32, vc32 = (t.astype(jnp.float32) for t in (qc, kc, vc))
        log_f = jax.nn.log_sigmoid(fc)           # (B,L,H)
        big_f = jnp.cumsum(log_f, axis=1)        # inclusive
        a = ic - big_f                           # (B,L,H)
        g = jnp.maximum(m_in[:, None, :],
                        jax.lax.cummax(a, axis=1))           # (B,L,H)
        m_t = big_f + g
        w = jnp.exp(a[:, None, :, :] - g[:, :, None, :])     # (B,t,j,H)
        idx = jnp.arange(qc.shape[1])
        causal = (idx[:, None] >= idx[None, :])[None, :, :, None]
        w = w * causal.astype(w.dtype)
        u = jnp.exp(m_in[:, None, :] - g)                    # (B,L,H)

        scores = jnp.einsum("bihk,bjhk->bijh", qc32, kc32)   # (B,t,j,H)
        ws = w * scores
        num = (jnp.einsum("bijh,bjhv->bihv", ws, vc32)
               + u[..., None] * jnp.einsum("bihk,bhkv->bihv", qc32, chat))
        den = (jnp.sum(ws, axis=2)
               + u * jnp.einsum("bihk,bhk->bih", qc32, nhat))
        h_out = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]

        # chunk-final state, stabilized at m_out = m at the last position
        f_tot = big_f[:, -1, :]                              # (B,H)
        m_out = m_t[:, -1, :]
        decay_j = jnp.exp(f_tot[:, None, :] - big_f + ic
                          - m_out[:, None, :])               # (B,L,H)
        chat_new = (jnp.exp(f_tot + m_in - m_out)[:, :, None, None] * chat
                    + jnp.einsum("bjh,bjhk,bjhv->bhkv",
                                 decay_j, kc32, vc32))
        nhat_new = (jnp.exp(f_tot + m_in - m_out)[:, :, None] * nhat
                    + jnp.einsum("bjh,bjhk->bhk", decay_j, kc32))
        return (chat_new, nhat_new, m_out), h_out

    state, hs = jax.lax.scan(chunk_body, state, (q_c, k_c, v_c, i_c, f_c))
    h_full = hs.swapaxes(0, 1).reshape(b, nc * chunk, h, d)
    return h_full[:, :s], state


def mlstm_apply(params, cfg, x, *, cache: Optional[dict] = None,
                use_chunked: bool = True) -> Tuple[jax.Array,
                                                   Optional[dict]]:
    d_inner, heads, dh = mlstm_dims(cfg)
    b, s, _ = x.shape
    up = layers.dense(params["up"], x)
    xin, z = jnp.split(up, 2, axis=-1)

    def split_heads(t):
        return t.reshape(b, s, heads, dh)

    q = split_heads(layers.dense(params["q"], xin)) / jnp.sqrt(dh)
    k = split_heads(layers.dense(params["k"], xin)) / jnp.sqrt(dh)
    v = split_heads(layers.dense(params["v"], xin))
    i_pre = layers.dense(params["igate"], xin).astype(jnp.float32)   # (B,S,H)
    f_pre = (jnp.einsum("bsd,dh->bsh", xin.astype(jnp.float32),
                        params["fgate"]["w"]) + params["fgate"]["b"])

    if cache is not None:
        state = (cache["c"], cache["n"], cache["m"])
    else:
        state = (jnp.zeros((b, heads, dh, dh), jnp.float32),
                 jnp.zeros((b, heads, dh), jnp.float32),
                 jnp.full((b, heads), -1e30, jnp.float32))

    if use_chunked and s > 1:
        hmat, state = _mlstm_chunked(q, k, v, i_pre, f_pre, state)
        hflat = hmat.astype(x.dtype).reshape(b, s, d_inner)
        out = layers.dense(params["down"], hflat * jax.nn.silu(z))
        new_cache = ({"c": state[0], "n": state[1], "m": state[2]}
                     if cache is not None else None)
        return P.constrain(out, ("batch", "seq", "embed")), new_cache

    def step(carry, inp):
        c_mat, n_vec, m = carry
        qt, kt, vt, it, ft = inp                   # (B,H,dh) x3, (B,H) x2
        log_f = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(log_f + m, it)
        i_sc = jnp.exp(it - m_new)[:, :, None]
        f_sc = jnp.exp(log_f + m - m_new)[:, :, None]
        kt32, vt32, qt32 = (t.astype(jnp.float32) for t in (kt, vt, qt))
        c_new = f_sc[..., None] * c_mat + i_sc[..., None] * (
            kt32[..., :, None] * vt32[..., None, :])
        n_new = f_sc * n_vec + i_sc * kt32
        num = jnp.einsum("bhk,bhkv->bhv", qt32, c_new)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", qt32, n_new)),
                          1.0)[..., None]
        h = num / den
        return (c_new, n_new, m_new), h.astype(x.dtype)

    xs = (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
          i_pre.swapaxes(0, 1), f_pre.swapaxes(0, 1))
    state, hs = jax.lax.scan(step, state, xs)
    h = hs.swapaxes(0, 1).reshape(b, s, d_inner)
    out = layers.dense(params["down"], h * jax.nn.silu(z))
    new_cache = ({"c": state[0], "n": state[1], "m": state[2]}
                 if cache is not None else None)
    return P.constrain(out, ("batch", "seq", "embed")), new_cache


def mlstm_cache(cfg, batch: int):
    _, heads, dh = mlstm_dims(cfg)
    return {"c": jnp.zeros((batch, heads, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, heads, dh), jnp.float32),
            "m": jnp.full((batch, heads), -1e30, jnp.float32)}


# ------------------------------------------------------------------ sLSTM --
def slstm_dims(cfg):
    heads = cfg.lstm_heads
    return heads, cfg.d_model // heads


def slstm_init(key, cfg):
    heads, dh = slstm_dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    return {
        "wx": layers.dense_init(ks[0], d, 4 * d),
        "r": {"w": jax.random.normal(ks[1], (heads, dh, 4 * dh),
                                     jnp.float32) / jnp.sqrt(dh)},
        "fbias": jnp.full((heads, dh), 3.0, jnp.float32),
    }


def slstm_apply(params, cfg, x, *, cache: Optional[dict] = None,
                use_kernel: bool = False) -> Tuple[jax.Array,
                                                   Optional[dict]]:
    heads, dh = slstm_dims(cfg)
    b, s, d = x.shape
    wx = layers.dense(params["wx"], x).reshape(b, s, heads, 4 * dh)

    if cache is not None:
        state = (cache["c"], cache["n"], cache["m"], cache["h"])
    else:
        zero = jnp.zeros((b, heads, dh), jnp.float32)
        state = (zero, zero, jnp.full((b, heads, dh), -1e30, jnp.float32),
                 zero)

    if use_kernel and s > 1:
        # fused Pallas cell: recurrent weights VMEM-resident, in-kernel
        # time loop (TPU target; EXPERIMENTS §Perf P3 "next kernel")
        from repro.kernels import ops as kernel_ops
        hs_k, st = kernel_ops.slstm_cell(
            wx, params["r"]["w"], params["fbias"], *state)
        h = hs_k.reshape(b, s, d).astype(x.dtype)
        new_cache = ({"c": st[0], "n": st[1], "m": st[2], "h": st[3]}
                     if cache is not None else None)
        return P.constrain(h, ("batch", "seq", "embed")), new_cache

    r_w = params["r"]["w"]

    def step(carry, xt):
        c, n, m, h = carry
        rec = jnp.einsum("bhk,hkf->bhf", h, r_w)           # (B,H,4dh)
        pre = xt.astype(jnp.float32) + rec
        i_pre, f_pre, z_pre, o_pre = jnp.split(pre, 4, axis=-1)
        f_pre = f_pre + params["fbias"][None]
        log_f = jax.nn.log_sigmoid(f_pre)
        m_new = jnp.maximum(log_f + m, i_pre)
        i_sc = jnp.exp(i_pre - m_new)
        f_sc = jnp.exp(log_f + m - m_new)
        c_new = f_sc * c + i_sc * jnp.tanh(z_pre)
        n_new = jnp.maximum(f_sc * n + i_sc, 1e-6)
        h_new = jax.nn.sigmoid(o_pre) * c_new / n_new
        return (c_new, n_new, m_new, h_new), h_new

    # remat the cell: without it the scan stacks per-step gate residuals
    # (S x B x H x 4dh) for backward — the dominant HBM term at 4k train.
    # Recomputing the gates from the (small) carry is far cheaper.
    state, hs = jax.lax.scan(jax.checkpoint(step), state, wx.swapaxes(0, 1))
    h = hs.swapaxes(0, 1).reshape(b, s, d).astype(x.dtype)
    new_cache = ({"c": state[0], "n": state[1], "m": state[2],
                  "h": state[3]} if cache is not None else None)
    return P.constrain(h, ("batch", "seq", "embed")), new_cache


def slstm_cache(cfg, batch: int):
    heads, dh = slstm_dims(cfg)
    zero = jnp.zeros((batch, heads, dh), jnp.float32)
    return {"c": zero, "n": zero,
            "m": jnp.full((batch, heads, dh), -1e30, jnp.float32), "h": zero}

"""Plain reference of the xLSTM family (``xlstm-125m``), in float32.

mLSTM blocks (up-projection to 2 x 2d, q/k/v from the first half,
exponential input gate and sigmoid forget gate, stabilized matrix memory
in its parallel form, output gated by SiLU of the second half) and sLSTM
blocks (recurrent exponential gating per head, normalizer state, then a
SiLU-gated MLP of width int(4d/3)), in the configuration's
``block_pattern``. Each departure of the program from the published model
is listed in the configuration file's ``departures``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import (Matmul, cross_entropy, layer_params,
                              logits_of, mlp, rmsnorm)


def mlstm(mm, p, x, cfg):
    b, s, d = x.shape
    heads = cfg["num_heads"]
    d_inner = cfg["mlstm_proj_factor"] * d
    dh = d_inner // heads
    xin, z = jnp.split(mm("bsd,df->bsf", x, p["up"]["w"]), 2, axis=-1)

    def heads_of(t):
        return t.reshape(b, s, heads, dh).transpose(0, 2, 1, 3)  # (B,H,S,dh)

    q = heads_of(mm("bsd,df->bsf", xin, p["q"]["w"])) / jnp.sqrt(float(dh))
    k = heads_of(mm("bsd,df->bsf", xin, p["k"]["w"])) / jnp.sqrt(float(dh))
    v = heads_of(mm("bsd,df->bsf", xin, p["v"]["w"]))
    i_pre = mm("bsd,dh->bhs", xin, p["igate"]["w"])
    f_pre = (mm("bsd,dh->bsh", xin, p["fgate"]["w"])
             + p["fgate"]["b"]).transpose(0, 2, 1)
    # log weight of key j at query t: i_j + sum_{j<u<=t} log f_u; the
    # stabilizer m_t is its maximum over j <= t
    big_f = jnp.cumsum(jax.nn.log_sigmoid(f_pre), axis=-1)      # (B,H,S)
    a = i_pre - big_f
    causal = jnp.tril(jnp.ones((s, s), bool))
    log_w = jnp.where(causal, a[:, :, None, :], -jnp.inf)
    m = jnp.max(log_w, axis=-1, keepdims=True)
    w = jnp.exp(log_w - m)                                       # (B,H,t,j)
    ws = w * mm("bhtk,bhjk->bhtj", q, k)
    num = mm("bhtj,bhjv->bhtv", ws, v)
    den = jnp.maximum(jnp.abs(jnp.sum(ws, axis=-1)), 1.0)[..., None]
    h = (num / den).transpose(0, 2, 1, 3).reshape(b, s, d_inner)
    return mm("bsf,fd->bsd", h * jax.nn.silu(z), p["down"]["w"])


def slstm(mm, p, x, cfg):
    b, s, d = x.shape
    heads = cfg["num_heads"]
    dh = d // heads
    wx = mm("bsd,df->bsf", x, p["wx"]["w"]).reshape(b, s, heads, 4 * dh)
    r_w, fbias = p["r"]["w"], p["fbias"]

    def step(carry, xt):
        c, n, m, h = carry
        pre = xt + mm("bhk,hkf->bhf", h, r_w)
        i_pre, f_pre, z_pre, o_pre = jnp.split(pre, 4, axis=-1)
        log_f = jax.nn.log_sigmoid(f_pre + fbias[None])
        m_new = jnp.maximum(log_f + m, i_pre)
        i_sc = jnp.exp(i_pre - m_new)
        f_sc = jnp.exp(log_f + m - m_new)
        c_new = f_sc * c + i_sc * jnp.tanh(z_pre)
        n_new = jnp.maximum(f_sc * n + i_sc, 1e-6)
        h_new = jax.nn.sigmoid(o_pre) * c_new / n_new
        return (c_new, n_new, m_new, h_new), h_new

    zero = jnp.zeros((b, heads, dh), jnp.float32)
    init = (zero, zero, jnp.full((b, heads, dh), -1e30, jnp.float32), zero)
    _, hs = jax.lax.scan(step, init, wx.swapaxes(0, 1))
    return hs.swapaxes(0, 1).reshape(b, s, d)


def xlstm_block(kind, mm, p, x, cfg):
    eps = cfg["rms_norm_eps"]
    if kind == "mlstm":
        return x + mlstm(mm, p["cell"], rmsnorm(p["ln"]["scale"], x, eps),
                         cfg)
    x = x + slstm(mm, p["cell"], rmsnorm(p["ln"]["scale"], x, eps), cfg)
    return x + mlp(mm, p["mlp"], rmsnorm(p["ln2"]["scale"], x, eps))


def forward(params, tokens, cfg, mm: Matmul):
    """(B, S) tokens -> (B, S, V) float32 logits."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    pattern = cfg["block_pattern"]
    for i in range(cfg["num_hidden_layers"]):
        p = layer_params(params, i, len(pattern))
        x = xlstm_block(pattern[i % len(pattern)], mm, p, x, cfg)
    return logits_of(mm, params, x, cfg)


def lm_loss(params, tokens, labels, cfg, mm: Matmul):
    """Mean next-token cross-entropy over every position."""
    return cross_entropy(forward(params, tokens, cfg, mm), labels)

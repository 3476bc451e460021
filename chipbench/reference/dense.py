"""Plain reference of the dense family (``h2o-danube-1.8b``), in float32.

Pre-norm decoder blocks: RMSNorm, attention with rotary position
embeddings (the rotate-half form over the two halves of each head) and
grouped queries (query head ``h`` reads key/value head ``h // (heads /
kv_heads)``), causal within a sliding window (a query at position ``t``
sees keys ``t - window < s <= t``), scaled by ``1/sqrt(head_dim)``; then
RMSNorm and a SiLU-gated MLP; after the last block a final RMSNorm and
the output head (untied, ``lm_head``, when the configuration says so).

Attention is computed in blocks of ``BLOCK_Q`` queries, each against only
the keys its window reaches, and each block and each layer is recomputed
in the backward pass (``jax.checkpoint``): at 8192 positions the score
matrices, and the activations a backward pass would keep, do not fit
beside the reference's state. None of it changes the arithmetic of a
score, a softmax or a layer. Each departure of the program from the
published model is listed in the configuration file's ``departures``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import (Matmul, cross_entropy, layer_params, mlp,
                              rmsnorm)

BLOCK_Q = 512


def rope(x, positions, theta):
    """x (B, S, H, hd), positions (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(mm, p, x, cfg):
    b, s, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // h)
    window = cfg.get("sliding_window") or s
    pos = jnp.arange(s)
    q = rope(mm("bsd,df->bsf", x, p["q"]["w"]).reshape(b, s, h, hd), pos,
             cfg["rope_theta"])
    k = rope(mm("bsd,df->bsf", x, p["k"]["w"]).reshape(b, s, kv, hd), pos,
             cfg["rope_theta"])
    v = mm("bsd,df->bsf", x, p["v"]["w"]).reshape(b, s, kv, hd)

    def block(q_blk, k_blk, v_blk, q0, k0):
        sq, sk = q_blk.shape[1], k_blk.shape[1]
        qp = q0 + jnp.arange(sq)[:, None]
        kp = k0 + jnp.arange(sk)[None, :]
        seen = (kp <= qp) & (qp - kp < window)                     # (sq, sk)
        scores = mm("bqkgd,bskd->bkgqs",
                    q_blk.reshape(b, sq, kv, h // kv, hd), k_blk)
        scores = jnp.where(seen, scores / jnp.sqrt(float(hd)), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return mm("bkgqs,bskd->bqkgd", probs, v_blk).reshape(b, sq, h * hd)

    outs = []
    for q0 in range(0, s, BLOCK_Q):
        q1 = min(s, q0 + BLOCK_Q)
        k0 = max(0, q0 - window + 1)
        outs.append(jax.checkpoint(block, static_argnums=(3, 4))(
            q[:, q0:q1], k[:, k0:q1], v[:, k0:q1], q0, k0))
    return mm("bsf,fd->bsd", jnp.concatenate(outs, axis=1), p["o"]["w"])


def forward(params, tokens, cfg, mm: Matmul):
    """Logits (B, S, V) of ``tokens`` (B, S)."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def block(p, x):
        x = x + attention(mm, p["attn"], rmsnorm(p["ln1"]["scale"], x, eps),
                          cfg)
        return x + mlp(mm, p["mlp"], rmsnorm(p["ln2"]["scale"], x, eps))

    x = params["embed"]["table"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = block(layer_params(params, i, 1), x)
    x = rmsnorm(params["final_norm"]["scale"], x, eps)
    if cfg["tie_word_embeddings"]:
        return mm("bsd,vd->bsv", x, params["embed"]["table"])
    return mm("bsd,dv->bsv", x, params["lm_head"]["w"])


def lm_loss(params, tokens, labels, cfg, mm: Matmul):
    """Mean next-token cross-entropy over every position."""
    return cross_entropy(forward(params, tokens, cfg, mm), labels)

"""Uniform block interface over all architecture families.

Every block kind exposes
    init(kind, key, cfg)                    -> params
    apply(kind, params, cfg, x, ctx)        -> (x_new, new_cache, aux)
    make_cache(kind, cfg, batch, cache_len) -> cache pytree
so the model assembler (registry.py) can scan heterogeneous stacks without
knowing family internals. `aux` is a scalar side loss (MoE load balance),
zero elsewhere. Residual connections and pre-norms live here.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers, moe, ssm, xlstm


@dataclasses.dataclass(frozen=True)
class BlockCtx:
    positions: jax.Array                  # (B, S) or (B, S, 3)
    cache: Optional[dict] = None
    causal: bool = True
    window_override: Optional[int] = None  # long_500k SWA variant
    cross_kv: Optional[tuple] = None       # (k, v) for decoder cross-attn
    # positions are 0..S-1 in every row, built by the model (static)
    canonical_positions: bool = False


def _attn_dims(cfg):
    return layers.AttnDims(cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                           cfg.resolved_head_dim)


ATTN_KINDS = ("attn", "swa", "moe", "shared_attn", "xattn")


def init(kind: str, key, cfg):
    ks = jax.random.split(key, 8)
    if kind in ("attn", "swa", "shared_attn"):
        return {
            "ln1": layers.rmsnorm_init(cfg.d_model),
            "attn": layers.attention_init(ks[0], _attn_dims(cfg)),
            "ln2": layers.rmsnorm_init(cfg.d_model),
            "mlp": layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff),
        }
    if kind == "xattn":                    # decoder block with cross-attn
        return {
            "ln1": layers.rmsnorm_init(cfg.d_model),
            "attn": layers.attention_init(ks[0], _attn_dims(cfg)),
            "lnx": layers.rmsnorm_init(cfg.d_model),
            "xattn": layers.attention_init(ks[1], _attn_dims(cfg)),
            "ln2": layers.rmsnorm_init(cfg.d_model),
            "mlp": layers.mlp_init(ks[2], cfg.d_model, cfg.d_ff),
        }
    if kind == "moe":
        return {
            "ln1": layers.rmsnorm_init(cfg.d_model),
            "attn": layers.attention_init(ks[0], _attn_dims(cfg)),
            "ln2": layers.rmsnorm_init(cfg.d_model),
            "moe": moe.moe_init(ks[1], cfg.d_model, cfg.d_ff,
                                cfg.num_experts),
        }
    if kind == "mamba2":
        return {"ln": layers.rmsnorm_init(cfg.d_model),
                "mamba": ssm.mamba2_init(ks[0], cfg)}
    if kind == "mlstm":
        return {"ln": layers.rmsnorm_init(cfg.d_model),
                "cell": xlstm.mlstm_init(ks[0], cfg)}
    if kind == "slstm":
        d_ff = int(4 * cfg.d_model / 3)
        return {"ln": layers.rmsnorm_init(cfg.d_model),
                "cell": xlstm.slstm_init(ks[0], cfg),
                "ln2": layers.rmsnorm_init(cfg.d_model),
                "mlp": layers.mlp_init(ks[1], cfg.d_model, d_ff)}
    raise ValueError(f"unknown block kind {kind!r}")


def _window_for(kind: str, cfg, ctx: BlockCtx) -> Optional[int]:
    if ctx.window_override is not None:
        return ctx.window_override
    if kind == "swa":
        return cfg.sliding_window
    return None


def apply(kind: str, params, cfg, x, ctx: BlockCtx):
    zero = jnp.zeros((), jnp.float32)
    use_rope = cfg.pos_embedding == "rope"
    if kind in ("attn", "swa", "shared_attn", "moe"):
        h, new_cache = layers.attention_apply(
            params["attn"], _attn_dims(cfg),
            layers.rmsnorm(params["ln1"], x, cfg.norm_eps), ctx.positions,
            causal=ctx.causal, window=_window_for(kind, cfg, ctx),
            rope_theta=cfg.rope_theta,
            mrope_sections=(cfg.mrope_sections if use_rope else None),
            use_rope=use_rope, cache=ctx.cache,
            canonical_positions=ctx.canonical_positions)
        x = x + h
        if kind == "moe":
            y, aux = moe.moe_apply(
                params["moe"], layers.rmsnorm(params["ln2"], x, cfg.norm_eps),
                num_experts=cfg.num_experts,
                experts_per_token=cfg.experts_per_token,
                capacity_factor=cfg.capacity_factor)
            return x + y, new_cache, aux
        y = layers.mlp_apply(
            params["mlp"], layers.rmsnorm(params["ln2"], x, cfg.norm_eps))
        return x + y, new_cache, zero

    if kind == "xattn":
        dims = _attn_dims(cfg)
        self_cache = ctx.cache["self"] if ctx.cache is not None else None
        h, new_self = layers.attention_apply(
            params["attn"], dims,
            layers.rmsnorm(params["ln1"], x, cfg.norm_eps), ctx.positions,
            causal=True, use_rope=use_rope, cache=self_cache,
            canonical_positions=ctx.canonical_positions)
        x = x + h
        # cross-attention: project encoder output (train/prefill) or reuse
        # the cached per-layer cross KVs (decode).
        if ctx.cache is not None and "cross_k" in ctx.cache:
            cross_kv = (ctx.cache["cross_k"], ctx.cache["cross_v"])
        else:
            enc = ctx.cross_kv                       # raw (B, S_enc, D)
            b, s_enc, _ = enc.shape
            ck = layers.dense(params["xattn"]["k"], enc).reshape(
                b, s_enc, dims.num_kv_heads, dims.head_dim)
            cv = layers.dense(params["xattn"]["v"], enc).reshape(
                b, s_enc, dims.num_kv_heads, dims.head_dim)
            cross_kv = (ck, cv)
        h, _ = layers.attention_apply(
            params["xattn"], dims,
            layers.rmsnorm(params["lnx"], x, cfg.norm_eps), ctx.positions,
            kv_override=cross_kv)
        x = x + h
        y = layers.mlp_apply(
            params["mlp"], layers.rmsnorm(params["ln2"], x, cfg.norm_eps),
            activation="gelu")
        new_cache = None
        if ctx.cache is not None:
            new_cache = dict(ctx.cache)
            new_cache["self"] = new_self
        return x + y, new_cache, zero

    if kind == "mamba2":
        h, new_cache = ssm.mamba2_apply(
            params["mamba"], cfg,
            layers.rmsnorm(params["ln"], x, cfg.norm_eps), cache=ctx.cache)
        return x + h, new_cache, zero

    if kind == "mlstm":
        h, new_cache = xlstm.mlstm_apply(
            params["cell"], cfg,
            layers.rmsnorm(params["ln"], x, cfg.norm_eps), cache=ctx.cache)
        return x + h, new_cache, zero

    if kind == "slstm":
        h, new_cache = xlstm.slstm_apply(
            params["cell"], cfg,
            layers.rmsnorm(params["ln"], x, cfg.norm_eps), cache=ctx.cache)
        x = x + h
        y = layers.mlp_apply(
            params["mlp"], layers.rmsnorm(params["ln2"], x, cfg.norm_eps))
        return x + y, new_cache, zero

    raise ValueError(f"unknown block kind {kind!r}")


def make_cache(kind: str, cfg, batch: int, cache_len: int,
               window_override: Optional[int] = None, dtype=jnp.bfloat16):
    """Decode-time state for one block."""
    hd = cfg.resolved_head_dim
    if kind in ("attn", "moe", "swa", "shared_attn"):
        window = window_override if window_override is not None else (
            cfg.sliding_window if kind == "swa" else None)
        eff = min(cache_len, window) if window else cache_len
        return layers.init_kv_cache(batch, eff, cfg.num_kv_heads, hd, dtype)
    if kind == "xattn":
        return {"self": layers.init_kv_cache(batch, cache_len,
                                             cfg.num_kv_heads, hd, dtype)}
    if kind == "mamba2":
        return ssm.mamba2_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm.mlstm_cache(cfg, batch)
    if kind == "slstm":
        return xlstm.slstm_cache(cfg, batch)
    raise ValueError(f"unknown block kind {kind!r}")

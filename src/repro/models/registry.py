"""Model assembly: heterogeneous block stacks, scan-over-units, losses.

A config's layer stack is its ``block_unit`` repeated. To keep HLO size (and
512-device compile time) bounded, parameters of all full unit repetitions
are *stacked* (leading axis = repetition) and the stack is executed with one
``jax.lax.scan`` whose body unrolls the few blocks inside the unit; leftover
layers run unrolled. Weight-shared blocks (zamba2's shared attention) are
stored once and closed over by the scan body.

Public API:
  init_params(cfg, key)                       -> params pytree
  apply_model(params, cfg, batch, ...)        -> (logits, aux, new_caches)
  lm_loss(params, cfg, batch, ...)            -> (loss, metrics)
  init_cache(cfg, batch, cache_len, ...)      -> cache pytree
  count_params(cfg, active_only=False)        -> int
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import blocks, layers
from repro.models.blocks import BlockCtx
from repro.runtime import partitioning as P


# ------------------------------------------------------------- structure --
def segments(cfg) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
    unit = cfg.block_unit
    n_full = cfg.num_layers // len(unit)
    rem = cfg.block_kinds[n_full * len(unit):]
    return unit, n_full, rem


def sinusoidal_positions(positions, dim: int):
    """(B, S) int positions -> (B, S, dim) sinusoidal embeddings."""
    half = dim // 2
    freqs = jnp.exp(-np.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ------------------------------------------------------------------ init --
def init_params(cfg, key) -> Dict[str, Any]:
    unit, n_full, rem = segments(cfg)
    keys = jax.random.split(key, 16)
    params: Dict[str, Any] = {
        "embed": layers.embed_init(keys[0], cfg.vocab_size, cfg.d_model),
        "final_norm": layers.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(keys[5], cfg.d_model,
                                              cfg.vocab_size)
    stack: Dict[str, Any] = {"units": {}, "rem": {}}
    if "shared_attn" in cfg.block_kinds:
        stack["shared"] = blocks.init("shared_attn", keys[1], cfg)
    kidx = jax.random.split(keys[2], max(len(unit), 1) * max(n_full, 1)
                            + len(rem) + 1)
    ki = 0
    if n_full > 0:
        for i, kind in enumerate(unit):
            if kind == "shared_attn":
                continue
            layer_keys = kidx[ki: ki + n_full]
            ki += n_full
            stack["units"][f"p{i}"] = jax.vmap(
                lambda k, kind=kind: blocks.init(kind, k, cfg))(
                    jnp.stack(layer_keys))
    for i, kind in enumerate(rem):
        if kind == "shared_attn":
            continue
        stack["rem"][f"p{i}"] = blocks.init(kind, kidx[ki], cfg)
        ki += 1
    params["stack"] = stack

    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(keys[3], cfg.encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: blocks.init("attn", k, cfg))(enc_keys)
        params["enc_final_norm"] = layers.rmsnorm_init(cfg.d_model)
        # decoder blocks carry cross-attention: re-init stack with xattn kind
        dec_keys = jax.random.split(keys[4], cfg.num_layers)
        params["stack"] = {
            "units": {"p0": jax.vmap(
                lambda k: blocks.init("xattn", k, cfg))(dec_keys)},
            "rem": {},
        }
    return params


# ------------------------------------------------------- cache construction
def init_cache(cfg, batch: int, cache_len: int,
               window_override: Optional[int] = None,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    if cfg.is_encoder_decoder:
        per_layer = blocks.make_cache("xattn", cfg, batch, cache_len,
                                      window_override, dtype)
        hd = cfg.resolved_head_dim
        cross = {
            "cross_k": jnp.zeros((batch, cfg.source_positions,
                                  cfg.num_kv_heads, hd), dtype),
            "cross_v": jnp.zeros((batch, cfg.source_positions,
                                  cfg.num_kv_heads, hd), dtype),
        }
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x[None], (cfg.num_layers,) + x.shape), {**per_layer, **cross})
        return {"units": {"p0": stacked}, "rem": {}}

    unit, n_full, rem = segments(cfg)
    caches: Dict[str, Any] = {"units": {}, "rem": {}}
    for i, kind in enumerate(unit):
        if n_full == 0:
            break
        one = blocks.make_cache(kind, cfg, batch, cache_len,
                                window_override, dtype)
        caches["units"][f"p{i}"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n_full,) + x.shape), one)
    for i, kind in enumerate(rem):
        caches["rem"][f"p{i}"] = blocks.make_cache(
            kind, cfg, batch, cache_len, window_override, dtype)
    return caches


# ----------------------------------------------------------------- apply --
def _run_stack(params, cfg, x, positions, caches, *, causal=True,
               window_override=None, cross_kv=None,
               canonical_positions=False):
    unit, n_full, rem = segments(cfg)
    if cfg.is_encoder_decoder:
        unit, n_full, rem = ("xattn",), cfg.num_layers, ()
    stack = params["stack"]
    shared = stack.get("shared")
    aux = jnp.zeros((), jnp.float32)

    def make_ctx(cache):
        return BlockCtx(positions=positions, cache=cache, causal=causal,
                        window_override=window_override, cross_kv=cross_kv,
                        canonical_positions=canonical_positions)

    new_caches: Dict[str, Any] = {"units": {}, "rem": {}}
    if n_full > 0:
        has_cache = caches is not None
        xs = (stack["units"], caches["units"]) if has_cache \
            else stack["units"]

        def body(carry, scanned):
            xc, auxc = carry
            uparams, ucaches = scanned if has_cache else (scanned, None)
            ncs = {}
            for i, kind in enumerate(unit):
                p = shared if kind == "shared_attn" else uparams[f"p{i}"]
                c = ucaches[f"p{i}"] if has_cache else None
                xc, nc, a = blocks.apply(kind, p, cfg, xc, make_ctx(c))
                if has_cache:
                    ncs[f"p{i}"] = nc
                auxc = auxc + a
            return (xc, auxc), (ncs if has_cache else 0)

        # remat the unit body when training (no decode cache): activations
        # are recomputed in backward, so peak memory is ~one unit's worth.
        body_fn = body if has_cache else jax.checkpoint(body)
        (x, aux), scanned_out = jax.lax.scan(
            body_fn, (x, aux), xs)
        if has_cache:
            new_caches["units"] = scanned_out

    for i, kind in enumerate(rem):
        p = shared if kind == "shared_attn" else stack["rem"][f"p{i}"]
        c = caches["rem"][f"p{i}"] if caches is not None else None
        x, nc, a = blocks.apply(kind, p, cfg, x, make_ctx(c))
        if caches is not None:
            new_caches["rem"][f"p{i}"] = nc
        aux = aux + a
    return x, aux, (new_caches if caches is not None else None)


def _encode(params, cfg, frames):
    """Whisper-style encoder over stub frame embeddings (B, S_enc, D)."""
    b, s_enc, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(s_enc)[None], (b, s_enc))
    x = frames + sinusoidal_positions(pos, cfg.d_model).astype(frames.dtype)

    def body(xc, lparams):
        ctx = BlockCtx(positions=pos, cache=None, causal=False)
        xn, _, _ = blocks.apply("attn", lparams, cfg, xc, ctx)
        return xn, 0

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return layers.rmsnorm(params["enc_final_norm"], x, cfg.norm_eps)


def apply_model(params, cfg, batch: Dict[str, jax.Array], *,
                caches=None, window_override: Optional[int] = None,
                ) -> Tuple[jax.Array, jax.Array, Any]:
    """Forward pass. batch keys:
      tokens (B, S); positions (B, S) or (B, S, 3);
      vision_embeds (B, V, D) [vlm]; frames (B, S_enc, D) [audio].
    Returns (logits (B, S, V), aux_loss, new_caches)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = batch.get("positions")
    canonical = positions is None
    if canonical:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = layers.embed(params["embed"], tokens, dtype).astype(dtype)
    x = P.constrain(x, ("batch", "seq", "embed"))

    if cfg.vision_tokens and "vision_embeds" in batch:
        v = batch["vision_embeds"].astype(x.dtype)
        nv = v.shape[1]
        # stub layout: patch embeddings occupy the first V slots
        x = jnp.concatenate([v, x[:, nv:]], axis=1) if nv < s else v[:, :s]

    if cfg.pos_embedding == "sinusoidal":
        pos2d = positions if positions.ndim == 2 else positions[..., 0]
        x = x + sinusoidal_positions(pos2d, cfg.d_model).astype(x.dtype)

    cross_kv = None
    if cfg.is_encoder_decoder:
        if caches is not None:
            cross_kv = None   # per-layer cached cross KVs live in the cache
        else:
            enc_out = _encode(params, cfg, batch["frames"].astype(x.dtype))
            cross_kv = enc_out

    x, aux, new_caches = _run_stack(
        params, cfg, x, positions, caches, causal=True,
        window_override=window_override, cross_kv=cross_kv,
        canonical_positions=canonical)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x)
    else:
        logits = layers.lm_head(params["lm_head"], x)
    return logits, aux, new_caches


# ----------------------------------------------------------------- losses --
def lm_loss(params, cfg, batch, *, window_override=None,
            aux_weight: float = 0.01):
    logits, aux, _ = apply_model(params, cfg, batch,
                                 window_override=window_override)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = jnp.sum((logz - label_logit) * mask) / jnp.maximum(mask.sum(), 1.0)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux}


def prefill_cross_cache(params, cfg, frames, cache):
    """Encoder pass + per-decoder-layer cross-KV projection into the cache.

    frames: (B, S_enc, D) stub embeddings. Returns the cache with cross_k/v
    populated (leading stacked-layer axis), ready for decode_step.
    """
    enc = _encode(params, cfg, frames)                     # (B, S_enc, D)
    b, s_enc, _ = enc.shape
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    xattn = params["stack"]["units"]["p0"]["xattn"]        # stacked (L, ...)

    def project(wk, wv):
        ck = jnp.einsum("bsd,df->bsf", enc, wk.astype(enc.dtype))
        cv = jnp.einsum("bsd,df->bsf", enc, wv.astype(enc.dtype))
        return (ck.reshape(b, s_enc, kv, hd), cv.reshape(b, s_enc, kv, hd))

    ck, cv = jax.vmap(project)(xattn["k"]["w"], xattn["v"]["w"])
    unit_cache = dict(cache["units"]["p0"])
    unit_cache["cross_k"] = ck.astype(cache["units"]["p0"]["cross_k"].dtype)
    unit_cache["cross_v"] = cv.astype(cache["units"]["p0"]["cross_v"].dtype)
    return {"units": {"p0": unit_cache}, "rem": cache.get("rem", {})}


def decode_step(params, cfg, tokens, positions, caches, *,
                window_override=None):
    """One serving step: tokens (B, S_step) appended at `positions`."""
    logits, _, new_caches = apply_model(
        params, cfg, {"tokens": tokens, "positions": positions},
        caches=caches, window_override=window_override)
    return logits, new_caches


def build_positions(cfg, positions):
    """Canonical serving positions for this architecture.

    positions: (B, S) int32 absolute positions (-1 = padding / inactive).
    Returns the array ``apply_model``/``decode_step`` expect: (B, S) for
    scalar-RoPE archs, (B, S, 3) with the scalar broadcast across the
    (temporal, height, width) planes for M-RoPE (the text-only degenerate
    case). The ONE place serving builds positions — prefill, decode, and
    the scheduler all call it, instead of re-branching on
    ``cfg.mrope_sections`` per step (the old ``launch/serve.py`` bug
    surface)."""
    positions = jnp.asarray(positions, jnp.int32)
    if cfg.mrope_sections is not None:
        return jnp.broadcast_to(positions[..., None],
                                positions.shape + (3,))
    return positions


# ------------------------------------------------------------ accounting --
@functools.lru_cache(maxsize=64)
def _param_tree_shapes(cfg):
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return tree


def param_bucket_names(cfg) -> Tuple[str, ...]:
    """Canonical block-bucket names present in this architecture's param
    tree ("attn", "mlp", "embed", "norm", "ssm", "rest") — the vocabulary a
    ``groups="block:..."`` spec can name for this model (DESIGN.md
    §Groups). Derived from abstract shapes, no allocation."""
    from repro.core import packing
    return packing.tree_bucket_names(_param_tree_shapes(cfg))


def param_buckets(cfg) -> Dict[str, Tuple[str, ...]]:
    """Bucket name -> the leaf paths it claims, for spec debugging and the
    launcher's malformed-spec error messages."""
    from repro.core import packing
    out: Dict[str, list] = {}
    for path in packing.leaf_paths(_param_tree_shapes(cfg)):
        out.setdefault(packing.bucket_of(path), []).append(path)
    return {k: tuple(v) for k, v in sorted(out.items())}


def count_params(cfg, active_only: bool = False) -> int:
    tree = _param_tree_shapes(cfg)
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    total = 0
    for path, leaf in leaves:
        n = int(np.prod(leaf.shape))
        path_str = jax.tree_util.keystr(path)
        if active_only and ("'moe'" in path_str) and ("'router'" not in
                                                      path_str):
            n = int(n * cfg.experts_per_token / max(cfg.num_experts, 1))
        total += n
    return total

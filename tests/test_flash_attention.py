"""The training path's flash attention (``kernels/flash_attention``, the
splash kernel that ships with JAX) against ``models/layers.mha_blockwise``
and the float32 oracle ``kernels/ref.causal_attention_ref``: outputs and
gradients with respect to q, k and v, grouped-query attention with 4 query
heads a KV head, head dim 80, bf16 operands, with a sliding window shorter
than the sequence, none, and one at least as long. On the CPU the kernel
runs in interpret mode (``kernels.interpret_mode``).

Also: the kernel visits exactly the key blocks the mask needs, read from
its own block map; ``layers.attention_path`` takes the flash kernel only
where it may; and the ``attention_path`` counter reads each layer's path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.kernels import flash_attention as FA
from repro.kernels import ops
from repro.kernels.ref import causal_attention_ref
from repro.models import layers, registry
from repro.obs import metrics as obs_metrics

B, S, H, KV, D = 1, 2048, 8, 2, 80
SCALE = D ** -0.5

# Every side reads the same bf16 q, k and v. The kernel keeps scores and
# softmax statistics in float32 and accumulates its output, dq (over key
# blocks), dk and dv (over query blocks) in float32, rounding each to bf16
# once; mha_blockwise also rounds its scores and probabilities to bf16.
# Each may then part from the float32 oracle by a bf16 step (2^-8 relative)
# of its largest values, and the two from each other by two: the bound is
# 2^-6 of the largest reference magnitude. The kernel is held to be no
# further from the oracle than mha_blockwise, within half a step.
TOL = 2.0 ** -6
SLACK = 2.0 ** -9


def _inputs():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)
    return q, k, v, ct


def _flash(window, blocks=None):
    """The kernel at its own block sizes, or at ``blocks``."""
    def fn(q, k, v):
        qs = (q.astype(jnp.float32) * SCALE).astype(q.dtype)
        if blocks is None:
            return ops.flash_attention(qs, k, v, window=window)
        kernel = FA.make_kernel(S, H, window, blocks, True)
        return FA.apply_kernel(kernel, qs, k, v)
    return fn


def _blockwise(window):
    pos = jnp.arange(S)[None]
    return lambda q, k, v: layers.mha_blockwise(q, k, v, pos, pos, True,
                                                window)


def _reference(window):
    return lambda q, k, v: causal_attention_ref(
        q.astype(jnp.float32) * SCALE, k, v, window)


def _out_and_grads(fn, q, k, v, ct):
    out, vjp = jax.vjp(lambda *a: fn(*a).astype(jnp.float32), q, k, v)
    return [np.asarray(x, np.float32) for x in (out, *vjp(ct))]


@pytest.mark.parametrize("window,blocks", [
    (300, None), (None, None), (S, None), (1024, (256, 256))],
    ids=["window<s", "no-window", "window>=s", "window<s-5-key-blocks"])
def test_flash_matches_blockwise_and_reference(window, blocks):
    """The last case takes blocks of 256, so a query row reaches up to 5
    key blocks and dq sums as many blocks' shares."""
    q, k, v, ct = _inputs()
    if blocks is not None:
        blocks = FA.splash_blocks(*blocks)
    got = _out_and_grads(jax.jit(_flash(window, blocks)), q, k, v, ct)
    ref = _out_and_grads(jax.jit(_reference(window)), q, k, v, ct)
    blk = _out_and_grads(jax.jit(_blockwise(window)), q, k, v, ct)
    assert got[0].shape == (B, S, H, D)
    for name, g, r, b in zip(("out", "dq", "dk", "dv"), got, ref, blk):
        top = np.abs(r).max()
        err_flash = np.abs(g - r).max() / top
        err_blockwise = np.abs(b - r).max() / top
        assert err_flash <= TOL, (name, err_flash)
        assert err_blockwise <= TOL, (name, err_blockwise)
        assert np.abs(g - b).max() / top <= TOL, name
        assert err_flash <= err_blockwise + SLACK, (name, err_flash,
                                                    err_blockwise)


def _needed_blocks(s, window, bq, bkv):
    """Key blocks the mask needs over all query blocks: those holding a
    key some query of the block may see (``q - window < k <= q``)."""
    w = s if window is None else window
    n = 0
    for i in range(s // bq):
        first = max(i * bq - w + 1, 0)     # the block's first query's first
        last = (i + 1) * bq - 1            # key, and its last query's last
        n += last // bkv - first // bkv + 1
    return n


@pytest.mark.parametrize("s,window", [(S, 300), (S, None), (S, S),
                                      (8192, 4096), (8192, None)])
def test_kernel_visits_only_the_blocks_the_mask_needs(s, window):
    """Each kernel's block map (forward, dK/dV, dQ) holds as many blocks as
    the mask reaches, no more: at ``danube-train-ici4``'s 8192 positions
    and 4096 window, 30 of the 64 a full scan of 1024-query by 1024-key
    blocks would load."""
    blocks = FA.block_sizes(s, D)
    kernel = FA.make_kernel(s, H, window, blocks, True)
    need = _needed_blocks(s, window, blocks.block_q, blocks.block_kv)
    # one row for the heads' shared mask, and a zero entry for each grid
    # step whose block is skipped
    for info in (kernel.fwd_mask_info, kernel.dkv_mask_info,
                 kernel.dq_mask_info):
        block_map = np.asarray(info.block_mask)
        assert block_map.shape[0] == 1
        assert np.count_nonzero(block_map) == need
    assert np.asarray(kernel.fwd_mask_info.block_mask).shape[1] \
        == s // blocks.block_q
    if (s, window) == (8192, 4096):
        assert (blocks.block_q, blocks.block_kv) == (1024, 1024)
        assert need == 30


@pytest.mark.parametrize("s,tpu,canonical,cached,cross,causal,want", [
    (8192, True, True, False, False, True, "flash"),
    (4096, True, True, False, False, True, "flash"),
    (8192, False, True, False, False, True, "blockwise"),   # a CPU
    (8192, True, False, False, False, True, "blockwise"),   # given positions
    (8192, True, True, True, False, True, "blockwise"),     # a cache
    (8192, True, True, False, True, True, "blockwise"),     # cross-attention
    (8192, True, True, False, False, False, "blockwise"),   # bidirectional
    (2100, True, True, False, False, True, "blockwise"),    # no block divides
    (2048, True, True, False, False, True, "dense"),        # short s
    (16, True, True, True, False, True, "dense"),           # decode
])
def test_attention_path_selection(monkeypatch, s, tpu, canonical, cached,
                                  cross, causal, want):
    monkeypatch.setattr(layers, "_on_tpu", lambda: tpu)
    assert layers.attention_path(s, D, 32, 8, canonical=canonical,
                                 causal=causal, cached=cached,
                                 cross=cross) == want


def _path_counts(fn, *args):
    c = obs_metrics.attention_path_counter()
    before = dict(c.series())
    jax.eval_shape(fn, *args)
    return {k: v - before.get(k, 0) for k, v in c.series().items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("tpu,want", [(False, "blockwise"), (True, "flash")],
                         ids=["cpu", "tpu"])
def test_lm_loss_counts_each_layer_path(monkeypatch, tpu, want):
    """One trace of the loss at a danube cut (published widths, 3 layers,
    8192 positions) reads its self-attention's path once per trace of the
    layer, and the stack scans its three layers, so once: blockwise on a
    CPU, flash where the platform is a TPU (only traced here)."""
    monkeypatch.setattr(layers, "_on_tpu", lambda: tpu)
    cfg = base.get_config("h2o-danube-1.8b").cut_depth(3)
    params = jax.eval_shape(lambda: registry.init_params(
        cfg, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    counts = _path_counts(
        lambda p, t: registry.lm_loss(p, cfg, {"tokens": t, "labels": t}),
        params, tok)
    assert counts == {f"{want},window": 1}
    # positions the batch gives are not canonical to the model
    counts = _path_counts(
        lambda p, t: registry.lm_loss(p, cfg, {"tokens": t, "labels": t,
                                               "positions": t}),
        params, tok)
    assert counts == {"blockwise,window": 1}

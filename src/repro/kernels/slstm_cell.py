"""Pallas TPU kernel: fused sLSTM cell — the recurrence runs INSIDE the
kernel with the recurrent weights resident in VMEM.

Why: the sLSTM recurrence h_{t-1} -> gates is truly sequential (EXPERIMENTS
§Perf P3); lowered as a lax.scan, every step re-reads the (dh, 4dh)
recurrent matrix R from HBM (~2.4 MB/layer/step -> the dominant xlstm
roofline term even after cell remat). This kernel keeps R (plus the gate
bias and the running state) in VMEM across the whole sequence: HBM traffic
collapses to the wx stream + the h output, i.e. state-only traffic.

Tiling: grid (B/bb, H, S/sc) over head-major, time-leading copies of the
operands, with the sequence chunks as the LAST (sequential) grid
dimension; the state outputs map every s-chunk to the same block, so they
persist across chunks (the standard revisited-output accumulator
pattern). Inside a chunk the time loop is a fori_loop over the
VMEM-resident wx block; the per-step recurrent matmul (bb, dh) x (dh, 4dh)
runs on the MXU.

Semantics are identical to ``xlstm.slstm_apply``'s scan (oracle:
``ref.slstm_cell_ref``; parity-tested in tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

BLOCK_B = 8
CHUNK_S = 128


def _cell_kernel(s_valid, wx_ref, r_ref, fb_ref, c0_ref, n0_ref, m0_ref,
                 h0_ref, hs_ref, c_ref, n_ref, m_ref, h_ref):
    # one head's blocks: wx (sc, bb, 4dh) and hs (sc, bb, dh) with time on
    # the leading axis, the state (bb, dh), R (dh, 4dh), the bias (1, dh)
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        c_ref[...] = c0_ref[...].astype(jnp.float32)
        n_ref[...] = n0_ref[...].astype(jnp.float32)
        m_ref[...] = m0_ref[...].astype(jnp.float32)
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    r_mat = r_ref[...].astype(jnp.float32)        # (dh, 4dh) — VMEM resident
    fbias = fb_ref[...].astype(jnp.float32)       # (1, dh)
    dh = r_mat.shape[0]
    sc = wx_ref.shape[0]

    def step(t, _):
        c = c_ref[...]
        n = n_ref[...]
        m = m_ref[...]
        h = h_ref[...]
        xt = wx_ref[t].astype(jnp.float32)                 # (bb, 4dh)
        rec = jnp.dot(h, r_mat, preferred_element_type=jnp.float32)
        pre = xt + rec
        i_pre = pre[:, 0 * dh:1 * dh]
        f_pre = pre[:, 1 * dh:2 * dh] + fbias
        z_pre = pre[:, 2 * dh:3 * dh]
        o_pre = pre[:, 3 * dh:4 * dh]
        log_f = jax.nn.log_sigmoid(f_pre)
        m_new = jnp.maximum(log_f + m, i_pre)
        i_sc = jnp.exp(i_pre - m_new)
        f_sc = jnp.exp(log_f + m - m_new)
        c_new = f_sc * c + i_sc * jnp.tanh(z_pre)
        n_new = jnp.maximum(f_sc * n + i_sc, 1e-6)
        h_new = jax.nn.sigmoid(o_pre) * c_new / n_new
        # padded tail steps (t_global >= s_valid) must not move the state
        live = (s_idx * sc + t) < s_valid
        c_ref[...] = jnp.where(live, c_new, c)
        n_ref[...] = jnp.where(live, n_new, n)
        m_ref[...] = jnp.where(live, m_new, m)
        h_ref[...] = jnp.where(live, h_new, h)
        hs_ref[t] = h_new.astype(hs_ref.dtype)
        return 0

    jax.lax.fori_loop(0, sc, step, 0)


@functools.partial(jax.jit, static_argnames=("block_b", "chunk_s",
                                             "interpret"))
def slstm_cell(wx: jax.Array, r_w: jax.Array, fbias: jax.Array,
               c0: jax.Array, n0: jax.Array, m0: jax.Array, h0: jax.Array,
               *, block_b: int = BLOCK_B, chunk_s: int = CHUNK_S,
               interpret: bool | None = None):
    """Fused sLSTM over a whole sequence.

    Args:
      wx: (B, S, H, 4dh) precomputed input projections.
      r_w: (H, dh, 4dh) recurrent weights; fbias: (H, dh).
      c0/n0/m0/h0: (B, H, dh) initial state.

    Returns:
      (hs (B, S, H, dh) f32, (c, n, m, h) final state).
    """
    b, s, h, dh4 = wx.shape
    dh = dh4 // 4
    bb = min(block_b, b)
    sc = min(chunk_s, s)
    b_pad = (-b) % bb
    s_pad = (-s) % sc
    # head-major layouts, time leading: the TPU tiling wants every block's
    # last two dimensions to be (batch rows, features), so a block never
    # takes a single row of the head axis there
    wx_p = jnp.pad(wx, ((0, b_pad), (0, s_pad), (0, 0), (0, 0)))
    wx_p = jnp.transpose(wx_p, (2, 1, 0, 3))               # (H, S, B, 4dh)
    state0 = [jnp.pad(t, ((0, b_pad), (0, 0), (0, 0)))
              for t in (c0, n0, m0, h0)]
    # padded m must stay the running max's identity
    if b_pad:
        state0[2] = state0[2].at[b:].set(-1e30)
    state0 = [jnp.transpose(t, (1, 0, 2)) for t in state0]  # (H, B, dh)
    sp, bp = wx_p.shape[1], wx_p.shape[2]

    grid = (bp // bb, h, sp // sc)
    wx_spec = pl.BlockSpec((None, sc, bb, dh4), lambda i, j, k: (j, k, i, 0))
    r_spec = pl.BlockSpec((None, dh, dh4), lambda i, j, k: (j, 0, 0))
    fb_spec = pl.BlockSpec((None, 1, dh), lambda i, j, k: (j, 0, 0))
    st_spec = pl.BlockSpec((None, bb, dh), lambda i, j, k: (j, i, 0))
    hs_spec = pl.BlockSpec((None, sc, bb, dh), lambda i, j, k: (j, k, i, 0))
    st_shape = jax.ShapeDtypeStruct((h, bp, dh), jnp.float32)

    hs, c, n, m, h_out = pl.pallas_call(
        functools.partial(_cell_kernel, s),
        grid=grid,
        in_specs=[wx_spec, r_spec, fb_spec] + [st_spec] * 4,
        out_specs=[hs_spec] + [st_spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((h, sp, bp, dh), jnp.float32)]
        + [st_shape] * 4,
        interpret=interpret_mode(interpret),
    )(wx_p, r_w, fbias.reshape(h, 1, dh), *state0)
    hs = jnp.transpose(hs, (2, 1, 0, 3))
    c, n, m, h_out = (jnp.transpose(t, (1, 0, 2)) for t in (c, n, m, h_out))
    return hs[:b, :s], (c[:b], n[:b], m[:b], h_out[:b])

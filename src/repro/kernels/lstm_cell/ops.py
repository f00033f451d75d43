"""Public wrapper for the fused LSTM cell.

``lstm_cell_fused`` is differentiable: the forward runs the Pallas kernel
(compiled on TPU, interpret mode on CPU) and a ``jax.custom_vjp`` supplies
the analytic LSTM-cell backward in fp32 jnp.  The kernel returns only
(h', c'), so its backward rebuilds the gates from the saved inputs (the
flash-style recompute) and hands them to the same gate adjoint the
wavefront's backward applies to the gates it stashed.  The backward's
parity against ``jax.grad`` of ``lstm_cell_ref`` is pinned by
``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.lstm_cell.kernel import lstm_cell_pallas


def _gates(x, h, wx, wh, b):
    """Pre-activation gates [B, 4, H] in fp32 (same math as kernel/ref)."""
    return (
        jnp.einsum("bi,igh->bgh", x.astype(jnp.float32), wx.astype(jnp.float32))
        + jnp.einsum("bj,jgh->bgh", h.astype(jnp.float32), wh.astype(jnp.float32))
        + b.astype(jnp.float32)
    )


def lstm_cell_gate_adjoint(x, h, c, gates, wx, wh, dh_new, dc_new):
    """Analytic fp32 adjoint of one LSTM cell from its fp32 pre-activation
    gates [B, 4, H] as the forward computed them: no gate GEMM, only the
    four the gradient needs.  The single source of truth for the cell's
    backward math: the wavefront's backward (``core/pipeline.py``) feeds it
    the gates its recompute pass stashed, and :func:`lstm_cell_adjoint`
    the gates it rebuilds.

    (x [B, In], h/c [B, H] previous state, dh_new/dc_new cotangents of the
    new state, all any dtype) -> fp32 (dx, dh, dc, dwx, dwh, db)."""
    dh_new = dh_new.astype(jnp.float32)
    dc_new = dc_new.astype(jnp.float32)
    gates = gates.astype(jnp.float32)
    i_s = jax.nn.sigmoid(gates[:, 0])
    f_s = jax.nn.sigmoid(gates[:, 1])
    g_t = jnp.tanh(gates[:, 2])
    o_s = jax.nn.sigmoid(gates[:, 3])
    cf = c.astype(jnp.float32)
    c_new = f_s * cf + i_s * g_t
    tc = jnp.tanh(c_new)
    # dL/dc' accumulates the direct cotangent and h' = o*tanh(c') path
    dc_tot = dc_new + dh_new * o_s * (1.0 - tc * tc)
    d_pre = jnp.stack(
        [
            dc_tot * g_t * i_s * (1.0 - i_s),          # i gate
            dc_tot * cf * f_s * (1.0 - f_s),           # f gate
            dc_tot * i_s * (1.0 - g_t * g_t),          # g gate
            dh_new * tc * o_s * (1.0 - o_s),           # o gate
        ],
        axis=1,
    )  # [B, 4, H]
    dx = jnp.einsum("bgh,igh->bi", d_pre, wx.astype(jnp.float32))
    dh = jnp.einsum("bgh,jgh->bj", d_pre, wh.astype(jnp.float32))
    dc = dc_tot * f_s
    dwx = jnp.einsum("bi,bgh->igh", x.astype(jnp.float32), d_pre)
    dwh = jnp.einsum("bj,bgh->jgh", h.astype(jnp.float32), d_pre)
    db = d_pre.sum(axis=0)
    return dx, dh, dc, dwx, dwh, db


def lstm_cell_adjoint(x, h, c, wx, wh, b, dh_new, dc_new):
    """Analytic fp32 adjoint of one LSTM cell, gates recomputed from the
    saved inputs (for a forward that kept none, as the fused kernel's):
    the fp32 gate GEMMs, then :func:`lstm_cell_gate_adjoint`.

    (x [B, In], h/c [B, H] previous state, dh_new/dc_new cotangents of the
    new state, all any dtype) -> fp32 (dx, dh, dc, dwx, dwh, db)."""
    return lstm_cell_gate_adjoint(x, h, c, _gates(x, h, wx, wh, b), wx, wh, dh_new, dc_new)


@functools.lru_cache(maxsize=None)
def _make_fused_cell(block_b: int, block_h: int, interpret: bool):
    @jax.custom_vjp
    def cell(x, h, c, wx, wh, b):
        return lstm_cell_pallas(x, h, c, wx, wh, b, block_b=block_b, block_h=block_h, interpret=interpret)

    def fwd(x, h, c, wx, wh, b):
        return cell(x, h, c, wx, wh, b), (x, h, c, wx, wh, b)

    def bwd(res, cts):
        leaves = lstm_cell_adjoint(*res, *cts)
        return tuple(g.astype(a.dtype) for g, a in zip(leaves, res))

    cell.defvjp(fwd, bwd)
    return cell


def lstm_cell_fused(x, h, c, wx, wh, b, *, block_b: int = kernels.LSTM_BLOCK, block_h: int = kernels.LSTM_BLOCK):
    """Drop-in replacement for the models/lstm.py cell math.

    x [B, In], h/c [B, H], wx [In, 4, H], wh [H, 4, H], b [4, H] ->
    (h', c').  Requested blocks are clamped to the largest exact tile;
    differentiable via the analytic custom-vjp backward.  Compiled on a
    TPU, interpreted elsewhere (:func:`repro.kernels.interpret_default`).
    """
    bb = kernels.fit_block(x.shape[0], block_b)
    bh = kernels.fit_block(h.shape[1], block_h)
    return _make_fused_cell(bb, bh, kernels.interpret_default())(x, h, c, wx, wh, b)

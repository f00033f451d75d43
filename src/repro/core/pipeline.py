"""Wavefront (systolic) pipeline parallelism for stacked LSTMs — the
paper's model parallelism, faithfully — executed under an explicit
:class:`repro.core.schedule.PipelineSchedule`.

The paper places each LSTM layer on its own GPU (Fig. 2/3); node (layer l,
time t) starts as soon as (l-1, t) and (l, t-1) finish, so the stack fills a
diagonal wavefront.  On TPU we realize the same schedule with ``shard_map``
over the ``model`` mesh axis: stage s owns layers [s*Lp, (s+1)*Lp); a
``lax.scan`` over TT = k*S + NS - 1 clock ticks runs every stage in
lockstep, and a ``ppermute`` hands the stage-top hidden state to the next
stage each tick.  At tick τ stage s computes its layers for global
token-step u = τ - s (idle ticks are masked — the pipeline bubble is
(NS-1)/TT, which the roofline's compute term exposes honestly).

Removing input-feeding is precisely what makes the *decoder* runnable
through this pipeline (the paper's §3.2): with input-feeding the first layer
at t+1 needs the attention output at t, which lives after the last layer —
the wavefront collapses to serial execution.  ``forward_input_feeding``
therefore never uses this module.

**Microbatch interleave** (DESIGN.md §1): with ``micro_batches=k`` the
batch splits into k slices that enter the wavefront back-to-back —
microbatch m's timestep t occupies global token-step ``u = m*S + t`` and
stage s computes it at tick ``tau = s + u``.  Recurrent state resets at
every ``t == 0`` (microbatches are independent batch slices), so the whole
step runs in ``k*S + NS - 1`` ticks: ONE fill/drain for the step instead of
the ``k*(S + NS - 1)`` a per-microbatch wavefront would pay.

**Schedule-driven backward** (DESIGN.md §4): the backward is no longer
autodiff's transpose of the forward scan (which stashes every one of the
``k*S`` token-steps' activations per stage).  ``pipeline_lstm`` carries a
``jax.custom_vjp`` whose backward executes the schedule's table contract:

* the forward saves only each stage's *boundary inputs* (the ppermuted
  hand-off sequence — one [B, H] vector per token-step, ~6·Lp× smaller
  than the per-layer gate/state stash);
* the backward runs over the schedule's backward groups
  (:attr:`PipelineSchedule.bwd_group_starts`): per group a recompute
  pass (phase A) rebuilds the member microbatches' forward from the saved
  boundaries, stashing that group's ``g*S`` token-steps — each layer's h
  and c outputs and, where the cell exposes them, its four gate
  pre-activations — then the mirrored backward wavefront (phase B) runs
  over the group from the stash, with a per-tick ``ppermute`` carrying the
  hand-off gradient down the stage chain.  Phase B computes no gate: with
  the jnp cell a tick runs the 4·Lp GEMMs the gradient needs (dx, dh, dWx,
  dWh per layer); the fused Pallas cell keeps its gates in VMEM, so its
  backward rebuilds them (6·Lp).  The recompute pass starts only once the loss side
  has sent its gradient back (:func:`_after_cotangent`), so its stash
  never sits beside the head's activations.

``gpipe`` has one group of all k microbatches: peak stash ``k*S``
token-steps per stage, exactly the table's (and the old autodiff's)
liveness.  ``1f1b`` has k groups of one: peak stash ``S`` token-steps —
within the table's ``min(k, NS)·S`` bound and independent of k, which is
what lets ``micro_batches`` scale without scaling backward memory.  The
two orders sum the same gradients (pure reordering; pinned at train-step
level by tests/test_plan.py) at the cost, for ``1f1b``, of one extra
fill/drain per group in the backward — the single-program price of the
memory bound.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.schedule import SCHEDULES, PipelineSchedule


def stack_pipeline_params(layer_params: List[dict], num_stages: int):
    """[{wx [in,4,H], wh [H,4,H], b [4,H]}] * L  ->  stacked trees with
    leading [NS, Lp] dims.  Layer-0's input rows are zero-padded up to the
    hidden size so all layers share one wx shape (the padded input slots
    carry zeros at runtime)."""
    L = len(layer_params)
    if L % num_stages:
        raise ValueError(f"{L} layers cannot split into {num_stages} stages")
    hidden = layer_params[0]["wh"].shape[0]
    in_max = max(p["wx"].shape[0] for p in layer_params)
    assert in_max <= hidden + hidden, "pipeline assumes in_dim <= 2*hidden"

    def padded_wx(p):
        wx = p["wx"]
        pad = in_max - wx.shape[0]
        return jnp.pad(wx, ((0, pad), (0, 0), (0, 0))) if pad else wx

    wx = jnp.stack([padded_wx(p) for p in layer_params]).reshape(num_stages, L // num_stages, in_max, 4, hidden)
    wh = jnp.stack([p["wh"] for p in layer_params]).reshape(num_stages, L // num_stages, hidden, 4, hidden)
    b = jnp.stack([p["b"] for p in layer_params]).reshape(num_stages, L // num_stages, 4, hidden)
    return {"wx": wx, "wh": wh, "b": b}, in_max


def _pad_to(a, width: int):
    """Zero-pad the last axis of ``a`` [B, n] up to ``width``."""
    return jnp.pad(a, ((0, 0), (0, width - a.shape[-1]))) if a.shape[-1] < width else a


def _make_cell(wx, wh, b, *, in_max: int, dt, stage_kernel: str):
    """The per-tick stage cell: (l, x_in, h_prev, c_prev) -> (h, c, gates)
    with the plain einsum math, gates its fp32 pre-activations [B, 4, H];
    or -> (h, c) with the fused Pallas kernel, which keeps its gates in
    VMEM.  Shared by the forward scan and the backward's recompute pass,
    so the stash is bit-identical to the forward's carries."""
    from repro.kernels.lstm_cell.ops import lstm_cell_fused

    def cell(l, x_in, h_prev, c_prev):
        if stage_kernel != "jnp":
            # fused Pallas cell: gate GEMMs + state update in one kernel,
            # fed the stacked [in_max, 4, H] weights as-is (static gate
            # split).  h/c carries are fp32, so the kernel's outputs are
            # fp32 too.
            return lstm_cell_fused(x_in, h_prev, c_prev, wx[l], wh[l], b[l])
        gates = (
            jnp.einsum("bi,igh->bgh", x_in, wx[l].astype(dt))
            + jnp.einsum("bj,jgh->bgh", h_prev.astype(dt), wh[l].astype(dt))
            + b[l].astype(dt)
        ).astype(jnp.float32)
        i, f, g, o = gates[:, 0], gates[:, 1], gates[:, 2], gates[:, 3]
        c = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return h, c, gates

    return cell


def _stage_sweep(cell, Lp, first_in, h_in, c_in, *, dt, in_max):
    """Run a stage's Lp cells upward from the given carries — THE one copy
    of the layer sweep (inter-layer dtype cast included), run by the
    forward tick and by the backward's recompute pass.  Returns (hs [Lp,
    B, H], cs [Lp, B, H], stash): the stash holds one tuple per layer, its
    h and c outputs and, where the cell returns them, its gates — what
    the backward reads (:func:`_stashed_step`), one buffer each, so a
    gate GEMM can write its output straight into its slot."""
    cur = first_in
    hs, cs, stash = [], [], []
    for l in range(Lp):
        hl, cl, *gates = cell(l, _pad_to(cur, in_max), h_in[l], c_in[l])
        hs.append(hl)
        cs.append(cl)
        stash.append((hl, cl, *gates))
        cur = hl.astype(dt)  # the forward's inter-layer cast
    return jnp.stack(hs), jnp.stack(cs), tuple(stash)


def _stashed_step(stash, j, t):
    """Step ``j`` of the recompute pass's stash (:func:`_stage_sweep`'s,
    each buffer [G, ...] by step) -> per layer (h_in, c_in, h, gates): the
    carries that entered step j are step j-1's outputs, zero where a
    microbatch starts (t == 0); gates None where the cell kept them."""
    take = lambda a, i: jax.lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False)
    prev = jnp.maximum(j - 1, 0)
    return [
        (
            jnp.where(t == 0, 0.0, take(h_all, prev)),
            jnp.where(t == 0, 0.0, take(c_all, prev)),
            take(h_all, j),
            take(gates[0], j) if gates else None,
        )
        for h_all, c_all, *gates in stash
    ]


def _stage_bwd(wx, wh, b, first_in, layers, dtop, dh, dc, *, dt):
    """Analytic backward of one stage-tick (all Lp layers) from the
    recompute pass's stash, ``layers`` as :func:`_stashed_step` gives
    them: the carries that entered the step, its h outputs (each layer
    above 0 read the one below, cast as the forward cast it) and the
    gates.  With gates it runs the kernel package's gate adjoint
    (``kernels/lstm_cell/ops.py::lstm_cell_gate_adjoint``, the one source
    of truth for the cell math): four GEMMs a layer.  A cell that keeps
    its gates (the fused Pallas kernel) gets ``lstm_cell_adjoint``, which
    rebuilds them first in fp32: six.
    Returns (dfirst_in, dh_prev, dc_prev, dwx, dwh, db)."""
    from repro.kernels.lstm_cell.ops import lstm_cell_adjoint, lstm_cell_gate_adjoint

    Lp, in_max = wx.shape[0], wx.shape[1]
    hidden = wh.shape[1]
    xs = [first_in] + [_pad_to(layers[l][2].astype(dt), in_max) for l in range(Lp - 1)]
    # adjoint, top layer down
    dnext = dtop.astype(jnp.float32)  # grad flowing into layer l's h output
    dwx_l, dwh_l, db_l, dh_new, dc_new = [], [], [], [], []
    for l in reversed(range(Lp)):
        h_in, c_in, _, gates = layers[l]
        cts = (dnext + dh[l], dc[l])
        if gates is None:
            adj = lstm_cell_adjoint(xs[l], h_in, c_in, wx[l], wh[l], b[l], *cts)
        else:
            adj = lstm_cell_gate_adjoint(xs[l], h_in, c_in, gates, wx[l], wh[l], *cts)
        dx_l, dh_l, dc_l, dwx_c, dwh_c, db_c = adj
        dwx_l.append(dwx_c)
        dwh_l.append(dwh_c)
        db_l.append(db_c)
        dh_new.append(dh_l)
        dc_new.append(dc_l)
        dnext = dx_l[:, :hidden] if l > 0 else dx_l
    stack_rev = lambda seq: jnp.stack(seq[::-1])
    return (
        dnext,                 # dfirst_in [B, in_max] (layer 0's input grad)
        stack_rev(dh_new),     # [Lp, B, H]
        stack_rev(dc_new),
        stack_rev(dwx_l),      # [Lp, in_max, 4, H]
        stack_rev(dwh_l),
        stack_rev(db_l),
    )


def _after_cotangent(x, lefts, dy):
    """``(x, lefts, dy)`` with the residuals released only once ``dy``
    exists.  The recompute pass reads nothing else of the step, so the
    scheduler could otherwise start it, and its stash, as early as right
    after the forward: beside the head's logits, the step's largest
    buffer.  Tied to ``dy``, it starts after the head's backward."""
    return jax.lax.optimization_barrier((x, lefts, dy))


def _last_stage(outs, num_stages: int):
    """The wavefront's output: its last stage's hidden states.  Where the
    model axis spans devices, the hybrid plan's reshard to the batch layout
    compiles onto this slice (GSPMD moves the phase boundary's collective to
    the op that makes its operand), so the slice is named as the hand-off."""
    with jax.named_scope("handoff"):
        return outs[num_stages - 1]


@functools.lru_cache(maxsize=32)
def _scheduled_pipeline(mesh: Mesh, sched: PipelineSchedule, *, model_axis: str,
                        batch_axes: tuple, in_max: int, hidden: int, stage_kernel: str):
    """Build the custom-vjp (stacked, x_padded) -> y executor for one
    (mesh, schedule, shape-statics) binding.  Cached so repeated train
    steps reuse one function identity (stable jit caching).

    ``zerobubble`` lowers through the same path as ``1f1b`` (group size 1):
    the table splits each backward unit into an input-grad B and a
    weight-grad W so W work fills the parallel timeline's bubble, but a
    single-program lockstep realization has no idle slot to fill — it
    performs W(s, u) fused immediately after B(s, u), a table-legal order
    (W has no dependents), so the split shows up in the table's timeline
    accounting while the executed gradients stay identical.

    ``interleaved`` (``sched.chunks > 1``) dispatches to the virtual-stage
    ring executor below."""
    if sched.chunks > 1:
        return _interleaved_pipeline(
            mesh, sched, model_axis=model_axis, batch_axes=batch_axes,
            in_max=in_max, hidden=hidden, stage_kernel=stage_kernel,
        )
    NS, S, k = sched.num_stages, sched.seq_len, sched.micro_batches
    TT = sched.forward_ticks
    perm_up = [(i, i + 1) for i in range(NS - 1)]
    perm_down = [(i + 1, i) for i in range(NS - 1)]
    vary = lambda a: jax.lax.pcast(a, tuple(mesh.axis_names), to="varying")

    # -- forward: the wavefront scan (one fill/drain per step) --------------

    def _fwd_stage_fn(save_boundaries: bool):
        def stage_fn(w, xloc):
            wx, wh, b = w["wx"][0], w["wh"][0], w["b"][0]
            Lp = wx.shape[0]
            stage = jax.lax.axis_index(model_axis)
            B_loc = xloc.shape[0]
            B_mb = B_loc // k
            xmb = xloc.reshape(k, B_mb, S, in_max)
            dt = xloc.dtype
            cell = _make_cell(wx, wh, b, in_max=in_max, dt=dt, stage_kernel=stage_kernel)

            def tick(carry, tau):
                h, c, left = carry  # h,c [Lp, B_mb, H] fp32; left [B_mb, H] from prev stage
                u = tau - stage  # global token-step: microbatch m = u // S, timestep t = u % S
                valid = (u >= 0) & (u < k * S)
                uc = jnp.clip(u, 0, k * S - 1)
                m, t = uc // S, uc % S
                x_m = jax.lax.dynamic_index_in_dim(xmb, m, axis=0, keepdims=False)
                x_t = jax.lax.dynamic_index_in_dim(x_m, t, axis=1, keepdims=False)
                # microbatches are independent slices: recurrent state resets at t == 0
                h_in = jnp.where(t == 0, jnp.zeros_like(h), h)
                c_in = jnp.where(t == 0, jnp.zeros_like(c), c)
                # stage 0 layer 0 input: the embedded token; other stages: handoff
                first_in = jnp.where(stage == 0, x_t, jnp.pad(left, ((0, 0), (0, in_max - hidden))))
                hs, cs, _ = _stage_sweep(cell, Lp, first_in, h_in, c_in, dt=dt, in_max=in_max)
                hs = jnp.where(valid, hs, h)  # idle (fill/drain) ticks keep the carries
                cs = jnp.where(valid, cs, c)
                top = hs[-1].astype(dt)  # [B_mb, H] this stage's output at tick tau
                nxt_left = jax.lax.ppermute(top, model_axis, perm_up)
                ys = (top, left) if save_boundaries else top
                return (hs, cs, nxt_left), ys

            h0 = vary(jnp.zeros((Lp, B_mb, hidden), jnp.float32))
            c0 = vary(jnp.zeros((Lp, B_mb, hidden), jnp.float32))
            left0 = vary(jnp.zeros((B_mb, hidden), dt))
            _, ys = jax.lax.scan(tick, (h0, c0, left0), jnp.arange(TT))
            tops = ys[0] if save_boundaries else ys
            # stage s's valid outputs occupy ticks [s, s + k*S); un-interleave the
            # microbatches locally so the batch order matches the input shard's.
            window = jax.lax.dynamic_slice_in_dim(tops, stage, k * S, axis=0)  # [k*S, B_mb, H]
            out = window.reshape(k, S, B_mb, hidden).transpose(0, 2, 1, 3).reshape(B_loc, S, hidden)
            if not save_boundaries:
                return out[None]
            # the boundary inputs this stage consumed: left entering tick τ
            # carries top(s-1) for token-step u = τ - s, so the same window
            # slice (garbage for stage 0, which reads x instead).
            lefts = ys[1]
            lwin = jax.lax.dynamic_slice_in_dim(lefts, stage, k * S, axis=0)
            return out[None], lwin.reshape(k, S, B_mb, hidden)[None]

        return stage_fn

    pspec = lambda tree: jax.tree.map(lambda _: P(model_axis), tree)
    bspec = P(batch_axes if batch_axes else None, None, None)
    param_tpl = {"wx": 0, "wh": 0, "b": 0}

    def _run_fwd(stacked, x, save_boundaries):
        out_specs = P(model_axis, batch_axes if batch_axes else None, None, None)
        if save_boundaries:
            out_specs = (out_specs, P(model_axis, None, None, batch_axes if batch_axes else None, None))
        return jax.shard_map(
            _fwd_stage_fn(save_boundaries), mesh=mesh,
            in_specs=(pspec(param_tpl), bspec), out_specs=out_specs, check_vma=False,
        )(stacked, x)

    # -- backward: the schedule's recompute groups + mirrored wavefront ----

    g = sched.bwd_group_size
    # numpy, not jnp: this builder is lru_cached and may first run under an
    # active trace — a jnp constant would leak that trace into later calls
    starts = np.asarray(sched.bwd_group_starts, np.int32)
    G = g * S
    Tb = G + NS - 1

    def _bwd_stage_fn(w, xloc, leftsloc, dyloc):
        wx, wh, b = w["wx"][0], w["wh"][0], w["b"][0]
        Lp = wx.shape[0]
        stage = jax.lax.axis_index(model_axis)
        B_loc = xloc.shape[0]
        B_mb = B_loc // k
        xmb = xloc.reshape(k, B_mb, S, in_max)
        dymb = dyloc.astype(jnp.float32).reshape(k, B_mb, S, hidden)
        lefts = leftsloc[0]  # [k, S, B_mb, H]
        dt = xloc.dtype
        cell = _make_cell(wx, wh, b, in_max=in_max, dt=dt, stage_kernel=stage_kernel)

        def stage_input(xg, lg, mi, t):
            """first_in for local microbatch mi (within the group), step t."""
            x_m = jax.lax.dynamic_index_in_dim(xg, mi, axis=0, keepdims=False)
            x_t = jax.lax.dynamic_index_in_dim(x_m, t, axis=1, keepdims=False)
            l_m = jax.lax.dynamic_index_in_dim(lg, mi, axis=0, keepdims=False)
            l_t = jax.lax.dynamic_index_in_dim(l_m, t, axis=0, keepdims=False)
            return jnp.where(stage == 0, x_t, jnp.pad(l_t, ((0, 0), (0, in_max - hidden))))

        def group_body(grad_acc, m0):
            xg = jax.lax.dynamic_slice_in_dim(xmb, m0, g, axis=0)   # [g, B_mb, S, in_max]
            lg = jax.lax.dynamic_slice_in_dim(lefts, m0, g, axis=0)  # [g, S, B_mb, H]
            dyg = jax.lax.dynamic_slice_in_dim(dymb, m0, g, axis=0)  # [g, B_mb, S, H]

            # phase A: recompute this group's forward, stashing each step's
            # h/c outputs and the cells' gates — g*S token-steps
            # live per stage, the schedule's liveness contract.
            def fstep(carry, j):
                h, c = carry
                mi, t = j // S, j % S
                first_in = stage_input(xg, lg, mi, t)
                h_in = jnp.where(t == 0, jnp.zeros_like(h), h)
                c_in = jnp.where(t == 0, jnp.zeros_like(c), c)
                hs, cs, stash = _stage_sweep(cell, Lp, first_in, h_in, c_in, dt=dt, in_max=in_max)
                return (hs, cs), stash

            h0 = vary(jnp.zeros((Lp, B_mb, hidden), jnp.float32))
            c0 = vary(jnp.zeros((Lp, B_mb, hidden), jnp.float32))
            _, stash = jax.lax.scan(fstep, (h0, c0), jnp.arange(G))

            # phase B: the mirrored backward wavefront over the group, the
            # hand-off gradient ppermuted DOWN the stage chain each tick.
            def bstep(carry, taub):
                dh, dc, dleft_in, dwx, dwh, db = carry
                v = taub - (NS - 1 - stage)
                valid = (v >= 0) & (v < G)
                vc = jnp.clip(v, 0, G - 1)
                j = G - 1 - vc
                mi, t = j // S, j % S
                layers = _stashed_step(stash, j, t)
                first_in = stage_input(xg, lg, mi, t)
                dy_m = jax.lax.dynamic_index_in_dim(dyg, mi, axis=0, keepdims=False)
                dy_t = jax.lax.dynamic_index_in_dim(dy_m, t, axis=1, keepdims=False)
                # a microbatch's backward starts at its LAST timestep: the
                # incoming recurrent grads belong to the previous microbatch
                dh_u = jnp.where(t == S - 1, jnp.zeros_like(dh), dh)
                dc_u = jnp.where(t == S - 1, jnp.zeros_like(dc), dc)
                # the stage-top grad: the loss side for the last stage, the
                # ppermuted hand-off grad from stage s+1 otherwise
                dtop = jnp.where(stage == NS - 1, dy_t, dleft_in)
                dfirst, dh_n, dc_n, dwx_c, dwh_c, db_c = _stage_bwd(
                    wx, wh, b, first_in, layers, dtop, dh_u, dc_u, dt=dt
                )
                vm = valid[None, None]
                dh = jnp.where(vm, dh_n, dh)
                dc = jnp.where(vm, dc_n, dc)
                dwx = dwx + jnp.where(valid, 1.0, 0.0) * dwx_c
                dwh = dwh + jnp.where(valid, 1.0, 0.0) * dwh_c
                db = db + jnp.where(valid, 1.0, 0.0) * db_c
                dfirst = jnp.where(valid, dfirst, jnp.zeros_like(dfirst))
                dleft_out = jax.lax.ppermute(dfirst[:, :hidden], model_axis, perm_down)
                return (dh, dc, dleft_out, dwx, dwh, db), dfirst

            dh0 = vary(jnp.zeros((Lp, B_mb, hidden), jnp.float32))
            dc0 = vary(jnp.zeros((Lp, B_mb, hidden), jnp.float32))
            dl0 = vary(jnp.zeros((B_mb, hidden), jnp.float32))
            (_, _, _, dwx, dwh, db), dfirsts = jax.lax.scan(
                bstep, (dh0, dc0, dl0) + grad_acc, jnp.arange(Tb)
            )
            # stage 0 processes v = 0..G-1 at ticks [NS-1, NS-1+G) with
            # j = G-1-v: slice its window, flip to ascending step order.
            dxg = dfirsts[NS - 1 : NS - 1 + G][::-1]  # [G, B_mb, in_max]
            return (dwx, dwh, db), dxg.reshape(g, S, B_mb, in_max)

        zeros_like_f32 = lambda a: jnp.zeros(a.shape, jnp.float32)
        acc0 = (vary(zeros_like_f32(wx)), vary(zeros_like_f32(wh)), vary(zeros_like_f32(b)))
        (dwx, dwh, db), dxgs = jax.lax.scan(group_body, acc0, starts)
        if batch_axes:
            # each batch shard saw B_loc of the batch: the param grads are
            # partial sums — one boundary psum each (what autodiff's
            # shard_map transpose used to insert for the replicated params)
            dwx, dwh, db = (jax.lax.psum(a, batch_axes) for a in (dwx, dwh, db))
        # rounds ascend through microbatches, so [n_groups, g, ...] -> [k, ...]
        dx = dxgs.reshape(k, S, B_mb, in_max).transpose(0, 2, 1, 3).reshape(B_loc, S, in_max)
        grads = {"wx": dwx[None], "wh": dwh[None], "b": db[None]}
        return grads, dx[None]

    def _run_bwd(stacked, x, lefts, dy):
        grads, dx_all = jax.shard_map(
            _bwd_stage_fn, mesh=mesh,
            in_specs=(
                pspec(param_tpl),
                bspec,
                P(model_axis, None, None, batch_axes if batch_axes else None, None),
                bspec,
            ),
            out_specs=(
                pspec(param_tpl),
                P(model_axis, batch_axes if batch_axes else None, None, None),
            ),
            check_vma=False,
        )(stacked, x, lefts, dy)
        grads = jax.tree.map(lambda gr, p: gr.astype(p.dtype), grads, stacked)
        return grads, dx_all[0].astype(x.dtype)

    @jax.custom_vjp
    def run(stacked, x):
        outs = _run_fwd(stacked, x, save_boundaries=False)
        return _last_stage(outs, NS)

    def run_fwd(stacked, x):
        outs, lefts = _run_fwd(stacked, x, save_boundaries=True)
        return _last_stage(outs, NS), (stacked, x, lefts)

    def run_bwd(res, dy):
        stacked, x, lefts = res
        return _run_bwd(stacked, *_after_cotangent(x, lefts, dy))

    run.defvjp(run_fwd, run_bwd)
    return run


def _interleaved_pipeline(mesh: Mesh, sched: PipelineSchedule, *, model_axis: str,
                          batch_axes: tuple, in_max: int, hidden: int, stage_kernel: str):
    """The ``interleaved`` executor: v = ``sched.chunks`` layer chunks per
    device over VS = v*NS VIRTUAL stages.  Chunk c on device s is virtual
    stage ``vs = c*NS + s`` — the standard round-robin assignment — so the
    stage chain walks the mesh as a RING: vs -> vs+1 is device s -> s+1 for
    s < NS-1 and device NS-1 -> device 0 (next chunk) at the wrap.  Each
    tick every device runs ALL its chunks (v sweeps of Lc = Lp/v layers —
    the same per-tick flops as one gpipe stage) on the pure VS-deep
    wavefront ``tick = vs + u``, which keeps the hand-off systolic: a
    chunk's input is produced exactly one tick before it is consumed, so
    one [v, B, H] ring ppermute per tick suffices (device 0 rolls the
    received chunks by +1: what device NS-1's chunk c produced feeds chunk
    c+1).  The backward mirrors it with the grads ppermuted down the ring.
    The table (gpipe at VS stages) prices this honestly: fill/drain grows
    to VS-1 thin ticks, and each device saves v boundary windows."""
    NS, S, k, v = sched.num_stages, sched.seq_len, sched.micro_batches, sched.chunks
    VS = v * NS
    TT = sched.forward_ticks  # k*S + VS - 1
    ring_up = [(i, (i + 1) % NS) for i in range(NS)]
    ring_down = [(i, (i - 1) % NS) for i in range(NS)]
    send_up = lambda a: a if NS == 1 else jax.lax.ppermute(a, model_axis, ring_up)
    send_down = lambda a: a if NS == 1 else jax.lax.ppermute(a, model_axis, ring_down)
    vary = lambda a: jax.lax.pcast(a, tuple(mesh.axis_names), to="varying")
    batch_p = batch_axes if batch_axes else None

    def _fwd_stage_fn(save_boundaries: bool):
        def stage_fn(w, xloc):
            wx, wh, b = w["wx"][0], w["wh"][0], w["b"][0]  # [v, Lc, ...]
            Lc = wx.shape[1]
            stage = jax.lax.axis_index(model_axis)
            B_loc = xloc.shape[0]
            B_mb = B_loc // k
            xmb = xloc.reshape(k, B_mb, S, in_max)
            dt = xloc.dtype
            cells = [
                _make_cell(wx[c], wh[c], b[c], in_max=in_max, dt=dt, stage_kernel=stage_kernel)
                for c in range(v)
            ]

            def tick(carry, tau):
                h, c, left = carry  # h,c [v, Lc, B_mb, H] fp32; left [v, B_mb, H]
                hs_new, cs_new, tops = [], [], []
                for ci in range(v):
                    vs = ci * NS + stage  # this chunk's virtual stage
                    u = tau - vs
                    valid = (u >= 0) & (u < k * S)
                    ucl = jnp.clip(u, 0, k * S - 1)
                    m, t = ucl // S, ucl % S
                    x_m = jax.lax.dynamic_index_in_dim(xmb, m, axis=0, keepdims=False)
                    x_t = jax.lax.dynamic_index_in_dim(x_m, t, axis=1, keepdims=False)
                    h_in = jnp.where(t == 0, jnp.zeros_like(h[ci]), h[ci])
                    c_in = jnp.where(t == 0, jnp.zeros_like(c[ci]), c[ci])
                    first_in = jnp.where(
                        vs == 0, x_t, jnp.pad(left[ci], ((0, 0), (0, in_max - hidden)))
                    )
                    hs, cs, _ = _stage_sweep(cells[ci], Lc, first_in, h_in, c_in, dt=dt, in_max=in_max)
                    hs_new.append(jnp.where(valid, hs, h[ci]))
                    cs_new.append(jnp.where(valid, cs, c[ci]))
                    tops.append(hs_new[-1][-1].astype(dt))
                tops = jnp.stack(tops)  # [v, B_mb, H]
                received = send_up(tops)
                # device 0 consumes device NS-1's chunk c as chunk c+1's input
                nxt_left = jnp.where(stage == 0, jnp.roll(received, 1, axis=0), received)
                ys = (tops, left) if save_boundaries else tops
                return (jnp.stack(hs_new), jnp.stack(cs_new), nxt_left), ys

            h0 = vary(jnp.zeros((v, Lc, B_mb, hidden), jnp.float32))
            c0 = vary(jnp.zeros((v, Lc, B_mb, hidden), jnp.float32))
            left0 = vary(jnp.zeros((v, B_mb, hidden), dt))
            _, ys = jax.lax.scan(tick, (h0, c0, left0), jnp.arange(TT))
            tops_hist = ys[0] if save_boundaries else ys  # [TT, v, B_mb, H]
            # the model output is virtual stage VS-1 = (chunk v-1, device
            # NS-1); its valid ticks occupy [VS-1, VS-1 + k*S)
            window = jax.lax.dynamic_slice_in_dim(
                tops_hist[:, v - 1], (v - 1) * NS + stage, k * S, axis=0
            )
            out = window.reshape(k, S, B_mb, hidden).transpose(0, 2, 1, 3).reshape(B_loc, S, hidden)
            if not save_boundaries:
                return out[None]
            lefts_hist = ys[1]
            lwins = [
                jax.lax.dynamic_slice_in_dim(lefts_hist[:, ci], ci * NS + stage, k * S, axis=0)
                .reshape(k, S, B_mb, hidden)
                for ci in range(v)
            ]
            return out[None], jnp.stack(lwins)[None]  # [1, v, k, S, B_mb, H]

        return stage_fn

    pspec = lambda tree: jax.tree.map(lambda _: P(model_axis), tree)
    bspec = P(batch_p, None, None)
    param_tpl = {"wx": 0, "wh": 0, "b": 0}

    def _run_fwd(stacked, x, save_boundaries):
        out_specs = P(model_axis, batch_p, None, None)
        if save_boundaries:
            out_specs = (out_specs, P(model_axis, None, None, None, batch_p, None))
        return jax.shard_map(
            _fwd_stage_fn(save_boundaries), mesh=mesh,
            in_specs=(pspec(param_tpl), bspec), out_specs=out_specs, check_vma=False,
        )(stacked, x)

    # -- backward: one gpipe-style group (all k microbatches), VS-deep -----

    G = k * S
    Tb = G + VS - 1

    def _bwd_stage_fn(w, xloc, leftsloc, dyloc):
        wx, wh, b = w["wx"][0], w["wh"][0], w["b"][0]
        Lc = wx.shape[1]
        stage = jax.lax.axis_index(model_axis)
        B_loc = xloc.shape[0]
        B_mb = B_loc // k
        xmb = xloc.reshape(k, B_mb, S, in_max)
        dymb = dyloc.astype(jnp.float32).reshape(k, B_mb, S, hidden)
        lefts = leftsloc[0]  # [v, k, S, B_mb, H]
        dt = xloc.dtype
        cells = [
            _make_cell(wx[c], wh[c], b[c], in_max=in_max, dt=dt, stage_kernel=stage_kernel)
            for c in range(v)
        ]

        def first_input(ci, mi, t):
            x_m = jax.lax.dynamic_index_in_dim(xmb, mi, axis=0, keepdims=False)
            x_t = jax.lax.dynamic_index_in_dim(x_m, t, axis=1, keepdims=False)
            l_m = jax.lax.dynamic_index_in_dim(lefts[ci], mi, axis=0, keepdims=False)
            l_t = jax.lax.dynamic_index_in_dim(l_m, t, axis=0, keepdims=False)
            vs = ci * NS + stage
            return jnp.where(vs == 0, x_t, jnp.pad(l_t, ((0, 0), (0, in_max - hidden))))

        # phase A: recompute every chunk's forward from its saved boundary
        # inputs (chunks recompute independently — their couplings are all
        # in the saved hand-offs), stashing each step's h/c outputs and
        # gates.
        def fstep(carry, j):
            h, c = carry  # [v, Lc, B_mb, H]
            mi, t = j // S, j % S
            sweeps = []
            for ci in range(v):
                h_in = jnp.where(t == 0, jnp.zeros_like(h[ci]), h[ci])
                c_in = jnp.where(t == 0, jnp.zeros_like(c[ci]), c[ci])
                sweeps.append(_stage_sweep(
                    cells[ci], Lc, first_input(ci, mi, t), h_in, c_in, dt=dt, in_max=in_max
                ))
            hs, cs, stash = zip(*sweeps)
            return (jnp.stack(hs), jnp.stack(cs)), stash

        h0 = vary(jnp.zeros((v, Lc, B_mb, hidden), jnp.float32))
        c0 = vary(jnp.zeros((v, Lc, B_mb, hidden), jnp.float32))
        _, stash = jax.lax.scan(fstep, (h0, c0), jnp.arange(G))  # per chunk, per layer

        # phase B: the mirrored VS-deep backward wavefront on the ring.
        def bstep(carry, taub):
            dh, dc, dleft_in, dwx, dwh, db = carry
            dh_new, dc_new, dwx_new, dwh_new, db_new, dfirsts = [], [], [], [], [], []
            for ci in range(v):
                vs = ci * NS + stage
                vb = taub - (VS - 1 - vs)
                valid = (vb >= 0) & (vb < G)
                vcl = jnp.clip(vb, 0, G - 1)
                j = G - 1 - vcl
                mi, t = j // S, j % S
                layers = _stashed_step(stash[ci], j, t)
                dy_m = jax.lax.dynamic_index_in_dim(dymb, mi, axis=0, keepdims=False)
                dy_t = jax.lax.dynamic_index_in_dim(dy_m, t, axis=1, keepdims=False)
                dh_u = jnp.where(t == S - 1, jnp.zeros_like(dh[ci]), dh[ci])
                dc_u = jnp.where(t == S - 1, jnp.zeros_like(dc[ci]), dc[ci])
                dtop = jnp.where(vs == VS - 1, dy_t, dleft_in[ci])
                dfirst, dh_n, dc_n, dwx_c, dwh_c, db_c = _stage_bwd(
                    wx[ci], wh[ci], b[ci], first_input(ci, mi, t), layers,
                    dtop, dh_u, dc_u, dt=dt,
                )
                vm = valid[None, None]
                dh_new.append(jnp.where(vm, dh_n, dh[ci]))
                dc_new.append(jnp.where(vm, dc_n, dc[ci]))
                g1 = jnp.where(valid, 1.0, 0.0)
                dwx_new.append(dwx[ci] + g1 * dwx_c)
                dwh_new.append(dwh[ci] + g1 * dwh_c)
                db_new.append(db[ci] + g1 * db_c)
                dfirsts.append(jnp.where(valid, dfirst, jnp.zeros_like(dfirst)))
            dfirsts = jnp.stack(dfirsts)  # [v, B_mb, in_max]
            received = send_down(dfirsts[:, :, :hidden])
            # device NS-1 consumes device 0's chunk c+1 grad as chunk c's
            dleft_out = jnp.where(stage == NS - 1, jnp.roll(received, -1, axis=0), received)
            carry_out = (
                jnp.stack(dh_new), jnp.stack(dc_new), dleft_out,
                jnp.stack(dwx_new), jnp.stack(dwh_new), jnp.stack(db_new),
            )
            return carry_out, dfirsts

        zeros_f32 = lambda a: vary(jnp.zeros(a.shape, jnp.float32))
        dh0 = vary(jnp.zeros((v, Lc, B_mb, hidden), jnp.float32))
        dc0 = vary(jnp.zeros((v, Lc, B_mb, hidden), jnp.float32))
        dl0 = vary(jnp.zeros((v, B_mb, hidden), jnp.float32))
        acc0 = (zeros_f32(wx), zeros_f32(wh), zeros_f32(b))
        (_, _, _, dwx, dwh, db), dfirsts_hist = jax.lax.scan(
            bstep, (dh0, dc0, dl0) + acc0, jnp.arange(Tb)
        )
        if batch_axes:
            dwx, dwh, db = (jax.lax.psum(a, batch_axes) for a in (dwx, dwh, db))
        # virtual stage 0 (device 0, chunk 0) emits dx at ticks
        # [VS-1, VS-1+G) with j = G-1-vb: slice, flip to ascending order
        dxg = dfirsts_hist[VS - 1 : VS - 1 + G, 0][::-1]  # [G, B_mb, in_max]
        dx = dxg.reshape(k, S, B_mb, in_max).transpose(0, 2, 1, 3).reshape(B_loc, S, in_max)
        grads = {"wx": dwx[None], "wh": dwh[None], "b": db[None]}
        return grads, dx[None]

    def _run_bwd(stacked, x, lefts, dy):
        grads, dx_all = jax.shard_map(
            _bwd_stage_fn, mesh=mesh,
            in_specs=(
                pspec(param_tpl),
                bspec,
                P(model_axis, None, None, None, batch_p, None),
                bspec,
            ),
            out_specs=(
                pspec(param_tpl),
                P(model_axis, batch_p, None, None),
            ),
            check_vma=False,
        )(stacked, x, lefts, dy)
        grads = jax.tree.map(lambda gr, p: gr.astype(p.dtype), grads, stacked)
        return grads, dx_all[0].astype(x.dtype)

    @jax.custom_vjp
    def run(stacked, x):
        outs = _run_fwd(stacked, x, save_boundaries=False)
        return _last_stage(outs, NS)

    def run_fwd(stacked, x):
        outs, lefts = _run_fwd(stacked, x, save_boundaries=True)
        return _last_stage(outs, NS), (stacked, x, lefts)

    def run_bwd(res, dy):
        stacked, x, lefts = res
        return _run_bwd(stacked, *_after_cotangent(x, lefts, dy))

    run.defvjp(run_fwd, run_bwd)
    return run


def pipeline_lstm(
    mesh: Mesh,
    stacked,
    x: jax.Array,
    *,
    in_dim: int,
    model_axis: str = "model",
    micro_batches: int = 1,
    stage_kernel: str = "jnp",
    schedule: str = "gpipe",
    virtual_stages: int = 1,
):
    """Run a stacked LSTM over ``x`` [B, S, in_dim] in wavefront order.

    ``stacked``: output of :func:`stack_pipeline_params` (leading [NS, Lp]).
    ``micro_batches=k`` splits the batch into k slices interleaved through
    ONE wavefront (k*S + NS - 1 ticks — fill/drain paid once per step).
    ``stage_kernel`` selects what computes each stage's cells per tick:
    ``"jnp"`` (plain einsum math) or ``"pallas"`` (the fused
    ``kernels/lstm_cell`` Pallas kernel — gate GEMMs + state update in one
    VMEM-resident kernel, compiled on a TPU and interpreted on the CPU;
    parity vs "jnp" is pinned by tests/test_plan.py).  ``schedule`` selects the
    :class:`~repro.core.schedule.PipelineSchedule` driving the backward's
    activation liveness: ``"gpipe"`` stashes all k microbatches at the
    fwd/bwd boundary, ``"1f1b"`` bounds the stash at one microbatch per
    stage (``min(k, NS)`` by the table), ``"zerobubble"`` rides 1f1b's
    groups with the backward's weight-grad/input-grad split priced by the
    table, and ``"interleaved"`` with ``virtual_stages=v > 1`` runs v layer
    chunks per device over the ring executor (each device's [Lp] rows are
    re-dealt round-robin to its chunks) — same gradients, different order,
    for all of them.  Returns hidden states of the top layer, [B, S, H].
    """
    from repro.core.plan import STAGE_KERNELS

    if stage_kernel not in STAGE_KERNELS:
        raise ValueError(f"stage_kernel must be one of {STAGE_KERNELS}, got {stage_kernel!r}")
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, got {schedule!r}")

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    num_stages = sizes[model_axis]
    batch_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    B, S, _ = x.shape
    dsz = 1
    for a in batch_axes:
        dsz *= sizes[a]
    k = micro_batches
    if B % (dsz * k):
        raise ValueError(f"batch {B} not divisible by batch shards x micro_batches = {dsz} x {k}")
    hidden = stacked["wh"].shape[2]
    in_max = stacked["wx"].shape[2]
    if in_dim < in_max:  # zero-pad the embedded inputs to the padded wx rows
        x = jnp.pad(x, ((0, 0), (0, 0), (0, in_max - in_dim)))
    if virtual_stages > 1 and schedule != "interleaved":
        raise ValueError(
            f"virtual_stages={virtual_stages} requires schedule='interleaved', got {schedule!r}"
        )
    chunks = virtual_stages if schedule == "interleaved" else 1
    if chunks > 1:
        Lp = stacked["wh"].shape[1]
        if Lp % chunks:
            raise ValueError(
                f"{Lp} layers/device cannot split into {chunks} virtual chunks"
            )
        # re-deal the contiguous [NS, Lp] rows to the round-robin virtual
        # assignment: device s's chunk c is virtual stage c*NS + s, i.e.
        # global layers [(c*NS+s)*Lc, ...) -> dev_stacked [NS, v, Lc, ...]
        VS, Lc = chunks * num_stages, Lp // chunks
        stacked = jax.tree.map(
            lambda a: a.reshape(VS, Lc, *a.shape[2:])
            .reshape(chunks, num_stages, Lc, *a.shape[2:])
            .transpose(1, 0, *range(2, a.ndim + 1)),
            stacked,
        )
    sched = PipelineSchedule(
        seq_len=S, num_stages=num_stages, micro_batches=k, kind=schedule, chunks=chunks
    )
    assert sched.forward_ticks == k * S + sched.virtual_stages - 1  # one fill/drain per STEP

    # Pin the stacked params replicated BEFORE the shard_map boundary.  When
    # the stacking (jnp.stack of the per-layer trees) is traced inside the
    # surrounding jit — the pipeline_backbone training path — GSPMD on jax
    # 0.4.x mispartitions the producing concatenate against the shard_map's
    # model-sharded operand spec and silently cross-sums the stages; an
    # explicit replicated constraint restores the documented layout (the
    # per-layer params ARE replicated) and the boundary reshard.
    stacked = jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(a, NamedSharding(mesh, P())), stacked
    )
    run = _scheduled_pipeline(
        mesh, sched, model_axis=model_axis, batch_axes=batch_axes,
        in_max=in_max, hidden=hidden, stage_kernel=stage_kernel,
    )
    return run(stacked, x)


def batch_shard_backbone(mesh: Mesh, batch_axes: tuple, dropout: float = 0.0):
    """Beyond-paper backbone (§Perf pair 3): run the stacked LSTMs inside a
    shard_map with the batch over ``batch_axes`` and parameters replicated.

    Under pjit, the scan backward all-reduces every LSTM weight grad each
    timestep (sum-of-psums over the batch shards; GSPMD cannot reassociate
    across the loop) — 2048 steps x 8 layers of ARs for the paper model.
    Inside shard_map the replicated params transpose to ONE boundary psum
    each: psum-of-sum, identical value, ~100x less collective traffic."""
    from repro.models import lstm as lstm_mod

    def run(layer_params, xs, rng):
        B = xs.shape[0]
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dsz = 1
        for a in batch_axes:
            dsz *= sizes[a]
        if not batch_axes:
            # nothing to shard over — the plain scan IS the requested layout
            return lstm_mod.run_stacked_lstm(layer_params, xs, dropout_rng=rng, dropout=dropout)[0]
        if B % dsz:
            # refuse rather than silently run the unsharded path (which
            # would change the collective structure the caller asked for)
            raise ValueError(
                f"batch {B} not divisible by batch shards {dsz} over axes "
                f"{batch_axes}; pad the batch or drop the batch-sharded backbone"
            )
        pspec = jax.tree.map(lambda _: P(), layer_params)
        xspec = P(batch_axes, None, None)

        def body(pl, xl):
            r = rng
            if r is not None:  # distinct dropout masks per batch shard
                for a in batch_axes:
                    r = jax.random.fold_in(r, jax.lax.axis_index(a))
            return lstm_mod.run_stacked_lstm(pl, xl, dropout_rng=r, dropout=dropout)[0]

        return jax.shard_map(body, mesh=mesh, in_specs=(pspec, xspec), out_specs=xspec, check_vma=False)(layer_params, xs)

    return run


def pipeline_backbone(mesh: Mesh, model_axis: str = "model", micro_batches: int = 1,
                      stage_kernel: str = "jnp", schedule: str = "gpipe",
                      virtual_stages: int = 1):
    """Adapter for ``seq2seq.forward_no_input_feeding(backbone=...)``: runs
    the stacked-LSTM encoder/decoder through the wavefront pipeline (with
    ``micro_batches`` slices interleaved through one fill/drain,
    ``stage_kernel`` selecting the per-tick cell compute, ``schedule`` the
    backward's activation liveness, and ``virtual_stages`` the interleaved
    layer chunks per device)."""

    def run(layer_params, xs, rng):  # rng unused: no dropout inside the pipeline
        del rng
        stacked, in_max = stack_pipeline_params(layer_params, dict(zip(mesh.axis_names, mesh.devices.shape))[model_axis])
        return pipeline_lstm(
            mesh, stacked, xs, in_dim=xs.shape[-1], model_axis=model_axis,
            micro_batches=micro_batches, stage_kernel=stage_kernel, schedule=schedule,
            virtual_stages=virtual_stages,
        )

    return run

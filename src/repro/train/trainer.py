"""ExecutionPlan-driven training step factory + a small host-side Trainer.

``make_train_step`` builds the jit'd step for any (architecture, plan).
The :class:`repro.core.plan.ExecutionPlan` owns every execution decision —
sharding specs, batch splitting, the microbatch schedule, and the overlap
flags; legacy keyword arguments (strat / mesh / micro_batches /
use_pipeline) are still accepted and folded into a plan for older call
sites.  The paper's hybrid phase switch enters through the plan's
``phase_boundary`` (and for the seq2seq MODEL / HYBRID strategies,
optionally the microbatch-interleaved wavefront pipeline backbone).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import strategy as stg
from repro.core.plan import ExecutionPlan
from repro.models import seq2seq as s2s
from repro.models import transformer as tfm
from repro.optim.optimizers import OptState, apply_updates, clip_by_global_norm


class LossScale(NamedTuple):
    """Dynamic loss-scale state (fp16 only).

    ``scale`` multiplies the loss before backward so small fp16 gradients
    survive the half-precision backward; grads are unscaled in fp32 before
    the optimizer.  ``good_steps`` counts consecutive overflow-free steps;
    after ``plan.loss_scale_growth`` of them the scale doubles, and any
    overflow halves it (floor 1.0) and resets the streak.
    """

    scale: jax.Array  # fp32 scalar
    good_steps: jax.Array  # int32 scalar


class TrainState(NamedTuple):
    params: Any
    opt_state: OptState
    scaling: Optional[LossScale] = None


def init_train_state(params, optimizer, plan: Optional[ExecutionPlan] = None, cfg=None) -> TrainState:
    """``scaling`` is present iff the plan resolves to fp16 compute —
    pytree structure (and thus jit shardings) must match the train step."""
    scaling = None
    if plan is not None and plan.fp16(cfg):
        scaling = LossScale(
            scale=jnp.asarray(plan.loss_scale_init, jnp.float32),
            good_steps=jnp.zeros((), jnp.int32),
        )
    return TrainState(params=params, opt_state=optimizer.init(params), scaling=scaling)


def state_shardings(specs, params_shapes, mesh: Optional[Mesh], strat: stg.Strategy, *, fp16: bool = False):
    """Shardings for TrainState: optimizer moments mirror the params.

    ``fp16`` must match the state's structure: a state carrying a LossScale
    needs a matching (replicated-scalar) LossScale here, or jit's pytree
    prefix match fails."""
    psh = stg.param_shardings(specs, params_shapes, mesh, strat)
    if mesh is None:
        return None
    scalar = NamedSharding(mesh, P())
    mom = psh
    scaling = LossScale(scale=scalar, good_steps=scalar) if fp16 else None
    return TrainState(
        params=psh,
        opt_state=OptState(step=scalar, m=mom, v=jax.tree.map(lambda s: s, mom)),
        scaling=scaling,
    )


def _sgd_v_fix(shardings, opt_state):
    """SGD keeps a scalar `v`; patch its sharding if the tree disagrees."""
    if shardings is None or not isinstance(opt_state.v, jax.Array):
        return shardings
    return shardings._replace(opt_state=shardings.opt_state._replace(v=shardings.opt_state.step))


def make_loss_fn(cfg: ModelConfig, plan: ExecutionPlan, *, remat: bool = True, pin_residual: bool = False, batch_backbone: bool = False):
    # Mixed precision enters here: the plan's compute_dtype overrides the
    # config's activation dtype for the whole forward/backward.  Parameters
    # stay fp32 (master weights) — the model casts them to the activation
    # dtype at each use site, so grad cotangents come back fp32.
    resolved = plan.resolve_compute_dtype(cfg)
    if resolved != cfg.dtype:
        cfg = dataclasses.replace(cfg, dtype=resolved)
    strat, mesh = plan.strategy, plan.mesh
    pb = plan.phase_boundary()
    if cfg.family == "seq2seq":
        backbone = plan.backbone(cfg, batch_backbone=batch_backbone)

        def loss_fn(params, batch, rng):
            b = s2s.Seq2SeqBatch(
                src=batch["src"],
                tgt_in=batch["tgt_in"],
                tgt_out=batch["tgt_out"],
                src_mask=batch["src_mask"],
                tgt_mask=batch["tgt_mask"],
            )
            kw = dict(dropout_rng=rng, phase_boundary=pb)
            if backbone is not None and not cfg.input_feeding:
                kw["backbone"] = backbone
            if plan.stage_kernel != "jnp":
                # the same plan switch that fuses the wavefront's LSTM cells
                # fuses the head's Luong attention (eq. 1-4)
                kw["stage_kernel"] = plan.stage_kernel
            loss, extras = s2s.forward(params, cfg, b, **kw)
            return loss, {"denom": extras["denom"]}

        return loss_fn

    ep = cfg.moe is not None and mesh is not None and strat != stg.Strategy.DATA
    ctx = tfm.RunCtx(
        mode="train",
        mesh=mesh if ep else None,
        ep_axis="model" if ep else None,
        data_axes=stg.data_axes(mesh) if mesh is not None else (),
        remat=remat,
        pin=stg.residual_pin(strat, mesh) if pin_residual else None,
        attn_mesh=mesh if (pin_residual and mesh is not None) else None,
        attn_shard_model=strat != stg.Strategy.DATA,
    )

    def loss_fn(params, batch, rng):
        del rng
        loss, extras = tfm.forward_train(
            params,
            cfg,
            batch["tokens"],
            batch["labels"],
            batch["mask"],
            frontend_embeds=batch.get("frontend"),
            ctx=ctx,
            phase_boundary=pb,
        )
        return loss, {"denom": extras["denom"], "aux": extras.get("aux", 0.0)}

    return loss_fn


def make_grad_fn(cfg: ModelConfig, plan: ExecutionPlan, *, remat: bool = True, pin_residual: bool = False, batch_backbone: bool = False):
    """(params, batch, rng) -> (loss, extras, grads) under the plan's
    microbatch schedule.

    * ``plan.accum_steps == 1`` (single batch, or a pipelined plan whose
      microbatches interleave inside ONE wavefront): one fwd/bwd.
    * otherwise: the global batch reshapes to [micro, B/micro, ...] and a
      ``lax.scan`` accumulates grads (one micro slice of activations live
      at a time).  Index-slicing the sharded batch dim instead makes GSPMD
      gather + replicate the compute — verified, 8x flops.
    * ``plan.overlap``: the hybrid head's grads are folded into the
      accumulator one microbatch LATE — the all-reduce that materializes
      microbatch i's (replicated) head grads is not needed until iteration
      i+1 consumes them, so it executes under i+1's backbone compute (the
      delayed psum at the paper's phase boundary).  The final sum is
      identical; only the reduction order moves.
    * ``plan.bucket_bytes``: generalizes the head-only delay to the whole
      tree — grads partition into size-targeted buckets and EVERY bucket's
      fold (and hence its all-reduce) is issued one microbatch late, so
      each bucket's sync overlaps the next microbatch's compute.  Pure
      reordering: the final sums are bitwise-order-equivalent per bucket.
    * ``scale`` (fp16 loss scaling): each microbatch's loss is multiplied
      by the scale before backward; the accumulated grads are divided by
      ``accum * scale`` in fp32 at the end.  The reported loss is always
      the UNSCALED mean.
    """
    loss_fn = make_loss_fn(cfg, plan, remat=remat, pin_residual=pin_residual, batch_backbone=batch_backbone)
    accum = plan.accum_steps

    def grads_of(params, batch, rng, scale=None):
        # bucket boundaries are shape-only — resolved at trace time
        buckets = plan.grad_buckets(params) if plan.bucket_bytes is not None else None
        def vg(p, mb, r):
            """One microbatch fwd/bwd; loss scaling applied inside."""
            if scale is None:
                (loss, extras), g = jax.value_and_grad(loss_fn, has_aux=True)(p, mb, r)
                return loss, extras, g

            def scaled(p_, mb_, r_):
                loss, extras = loss_fn(p_, mb_, r_)
                return loss * scale.astype(loss.dtype), (loss, extras)

            (_, (loss, extras)), g = jax.value_and_grad(scaled, has_aux=True)(p, mb, r)
            return loss, extras, g

        def finish(gsum):
            """fp32 unscale + mean; gsum is already fp32 (accumulated so
            from microbatch 0 — no trailing down-up cast round trip)."""
            if scale is None:
                return jax.tree.map(lambda g: g / accum, gsum)
            inv = 1.0 / (scale * accum)
            return jax.tree.map(lambda g: g.astype(jnp.float32) * inv, gsum)

        if accum == 1:
            loss, extras, grads = vg(params, batch, rng)
            if scale is not None:
                grads = jax.tree.map(lambda g: g.astype(jnp.float32) / scale, grads)
            return loss, extras, grads

        xs = plan.split_micro(batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        if not plan.overlap:
            def body(carry, mb):
                acc, loss_acc, denom_acc, i = carry
                loss, extras, g = vg(params, mb, jax.random.fold_in(rng, i))
                acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
                return (acc, loss_acc + loss, denom_acc + extras["denom"], i + 1), None

            (gsum, loss_sum, denom, _), _ = jax.lax.scan(body, (zeros, 0.0, 0.0, 0), xs)
            return loss_sum / accum, {"denom": denom}, finish(gsum)

        if buckets is not None:
            # bucketed delayed all-reduce: flat fp32 leaf lists in the
            # carry; each bucket folds microbatch i-1's grads while
            # microbatch i computes
            zl, treedef = jax.tree.flatten(zeros)
            order = [pos for bk in buckets for pos in bk["leaves"]]

            def body(carry, mb):
                acc, pending, loss_acc, denom_acc, i = carry
                loss, extras, g = vg(params, mb, jax.random.fold_in(rng, i))
                gl = jax.tree.leaves(g)
                acc = list(acc)
                pending = list(pending)
                for pos in order:
                    acc[pos] = acc[pos] + pending[pos]
                    pending[pos] = gl[pos].astype(jnp.float32)
                return (tuple(acc), tuple(pending), loss_acc + loss, denom_acc + extras["denom"], i + 1), None

            carry0 = (tuple(zl), tuple(zl), 0.0, 0.0, 0)
            (acc, pending, loss_sum, denom, _), _ = jax.lax.scan(body, carry0, xs)
            gsum = jax.tree.unflatten(treedef, [a + p for a, p in zip(acc, pending)])
            return loss_sum / accum, {"denom": denom}, finish(gsum)

        head0, body0 = ExecutionPlan.split_head(zeros)

        def body(carry, mb):
            acc_head, acc_body, pending, loss_acc, denom_acc, i = carry
            loss, extras, g = vg(params, mb, jax.random.fold_in(rng, i))
            g_head, g_body = ExecutionPlan.split_head(g)
            acc_body = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc_body, g_body)
            # fold in microbatch i-1's head grads: their all-reduce ran
            # under THIS microbatch's backbone compute
            acc_head = jax.tree.map(lambda a, b: a + b, acc_head, pending)
            pending = jax.tree.map(lambda x: x.astype(jnp.float32), g_head)
            return (acc_head, acc_body, pending, loss_acc + loss, denom_acc + extras["denom"], i + 1), None

        carry0 = (head0, body0, head0, 0.0, 0.0, 0)
        (acc_head, acc_body, pending, loss_sum, denom, _), _ = jax.lax.scan(body, carry0, xs)
        acc_head = jax.tree.map(lambda a, b: a + b, acc_head, pending)  # last microbatch's sync is exposed
        gsum = ExecutionPlan.merge_head(acc_head, acc_body)
        return loss_sum / accum, {"denom": denom}, finish(gsum)

    return grads_of


def make_train_step(
    cfg: ModelConfig,
    optimizer,
    *,
    plan: Optional[ExecutionPlan] = None,
    strat: stg.Strategy = stg.Strategy.SINGLE,
    mesh: Optional[Mesh] = None,
    specs=None,
    params_shapes=None,
    clip_norm: float = 5.0,
    use_pipeline: bool = False,
    remat: bool = True,
    micro_batches: int = 1,
    overlap: bool = False,
    schedule: str = "gpipe",
    pin_residual: bool = False,
    batch_backbone: bool = False,
    jit: bool = True,
):
    """Returns (train_step, state_shardings, batch_sharding_fn).

    ``plan`` carries every execution decision; when omitted, one is built
    from the legacy (strat, mesh, micro_batches, overlap, use_pipeline,
    schedule) kwargs.  See :func:`make_grad_fn` for how the plan's
    microbatch schedule is realized; the pipelined backward's activation
    liveness (``schedule``: gpipe vs 1f1b) is entirely the plan's and the
    pipeline executor's business — the trainer is untouched by the swap."""
    if plan is None:
        plan = ExecutionPlan(
            strategy=strat, mesh=mesh, micro_batches=micro_batches,
            overlap=overlap, use_pipeline=use_pipeline, schedule=schedule,
        )
    strat, mesh = plan.strategy, plan.mesh
    grads_of = make_grad_fn(cfg, plan, remat=remat, pin_residual=pin_residual, batch_backbone=batch_backbone)
    fp16 = plan.fp16(cfg)

    def train_step(state: TrainState, batch, lr_scale, rng):
        if not fp16:
            loss, extras, grads = grads_of(state.params, batch, rng)
            with jax.named_scope("optimizer"):
                grads, gnorm = clip_by_global_norm(grads, clip_norm)
                updates, opt_state = optimizer.update(grads, state.opt_state, state.params, lr_scale)
                params = apply_updates(state.params, updates)
            metrics = {"loss": loss, "grad_norm": gnorm, "tokens": extras["denom"]}
            if "aux" in extras:
                metrics["moe_aux"] = extras["aux"]
            return TrainState(params=params, opt_state=opt_state, scaling=state.scaling), metrics

        # fp16: dynamic loss scaling.  grads_of scales each microbatch's
        # loss and returns unscaled fp32 grads; a nonfinite leaf anywhere
        # means the scaled backward overflowed — skip the update, halve
        # the scale.  A streak of plan.loss_scale_growth clean steps
        # doubles it.
        scale = state.scaling.scale
        loss, extras, grads = grads_of(state.params, batch, rng, scale)
        with jax.named_scope("optimizer"):
            finite = jnp.array(True)
            for g in jax.tree.leaves(grads):
                finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            updates, opt_state_new = optimizer.update(grads, state.opt_state, state.params, lr_scale)
            params_new = apply_updates(state.params, updates)
            params = jax.tree.map(lambda n, o: jnp.where(finite, n, o), params_new, state.params)
            opt_state = jax.tree.map(lambda n, o: jnp.where(finite, n, o), opt_state_new, state.opt_state)
            good = jnp.where(finite, state.scaling.good_steps + 1, 0)
            grow = good >= plan.loss_scale_growth
            new_scale = jnp.where(
                finite,
                jnp.where(grow, scale * 2.0, scale),
                jnp.maximum(scale * 0.5, 1.0),
            )
            good = jnp.where(grow, jnp.zeros_like(good), good)
            scaling = LossScale(scale=new_scale, good_steps=good)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "tokens": extras["denom"],
            "loss_scale": new_scale,
            "overflow": 1.0 - finite.astype(jnp.float32),
        }
        if "aux" in extras:
            metrics["moe_aux"] = extras["aux"]
        return TrainState(params=params, opt_state=opt_state, scaling=scaling), metrics

    sshard = None
    if mesh is not None and specs is not None and params_shapes is not None:
        sshard = state_shardings(specs, params_shapes, mesh, strat, fp16=fp16)

    def batch_shardings(batch: dict):
        return plan.batch_shardings(batch)

    if jit:
        kw = {}
        if sshard is not None:
            kw = dict(in_shardings=(sshard, None, None, None), out_shardings=(sshard, None), donate_argnums=(0,))
        train_step = jax.jit(train_step, **kw)
    return train_step, sshard, batch_shardings


def _bucket(batch: dict) -> dict:
    """The batch's shape, as numbers on the ``trainer.dispatch`` span."""
    out = {"rows": next(iter(batch.values())).shape[0]}
    for key, name in (("src", "src_len"), ("tgt_in", "tgt_len")):
        if key in batch:
            out[name] = batch[key].shape[1]
    return out


class Trainer:
    """Minimal host loop: steps, periodic eval, plateau LR decay (paper)."""

    def __init__(self, cfg, optimizer, train_iter, *, plan=None, strat=stg.Strategy.SINGLE, mesh=None, specs=None, params=None, clip_norm=5.0, use_pipeline=False, seed=0):
        if plan is None:
            # build it here (not inside make_train_step) so init_train_state
            # sees the same fp16 decision as the step function
            plan = ExecutionPlan(strategy=strat, mesh=mesh, use_pipeline=use_pipeline)
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        self.step_fn, self.sshard, self.batch_sh = make_train_step(
            cfg, optimizer, plan=plan, specs=specs, params_shapes=shapes, clip_norm=clip_norm
        )
        self.state = init_train_state(params, optimizer, plan=plan, cfg=cfg)
        if self.sshard is not None:
            self.state = jax.device_put(self.state, self._patched_shard())
        self.train_iter = train_iter
        self.lr_scale = 1.0
        self.rng = jax.random.key(seed)
        self.history = []

    def _patched_shard(self):
        return _sgd_v_fix(self.sshard, self.state.opt_state)

    def run(self, steps: int, log_every: int = 50, log=print):
        """Runs ``steps`` steps.  Each step's host work is four profiler
        spans, seen only while a trace is being taken: ``trainer.fetch``
        (the next batch), ``trainer.put`` (its copies to the device, with
        their ``bytes``), ``trainer.dispatch`` (the step's launch, with the
        batch's ``rows`` and padded ``src_len``/``tgt_len``) and
        ``trainer.sync`` (the read-back of the step's metrics)."""
        import time

        t0 = time.perf_counter()
        tokens = 0.0
        for i in range(steps):
            with TraceAnnotation("trainer.fetch"):
                batch = next(self.train_iter)
            with TraceAnnotation("trainer.put", bytes=sum(v.nbytes for v in batch.values())):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            with TraceAnnotation("trainer.dispatch", **_bucket(batch)):
                self.rng, sub = jax.random.split(self.rng)
                self.state, metrics = self.step_fn(self.state, batch, self.lr_scale, sub)
            logging = (i + 1) % log_every == 0
            with TraceAnnotation("trainer.sync"):
                tokens += float(metrics["tokens"])
                if logging:
                    loss = float(metrics["loss"])
            if logging:
                dt = time.perf_counter() - t0
                self.history.append({"step": i + 1, "loss": loss, "tok_per_s": tokens / dt})
                log(f"step {i+1:5d}  loss {loss:.4f}  tok/s {tokens/dt:,.0f}  lr_scale {self.lr_scale:.3f}")
        return self.state

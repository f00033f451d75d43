"""Faults planted under a training cell's timed path, to show that the
check catches them.  Never used by a benchmark run: the fault tests and
``study.py`` plant them.

``plant`` replaces the ``Trainer``'s jitted step by one that computes what
the repository's step computes (gradients through the plan, global-norm
clipping, the optimizer) with one fault:

- ``unchanged``: the step returns the state it was given;
- ``half_batch``: the second half of the batch is left out, and the mean
  taken over the first;
- ``no_exchange``: the head's gradient is what one of the ``n`` devices
  would hold without the all-reduce: its own share of the rows, summed
  over the global token count (needs a mesh of ``n`` > 1 devices).
"""
from __future__ import annotations

import jax

FAULTS = ("unchanged", "half_batch", "no_exchange")


def plant(trainer, fault: str, cfg, plan, optimizer, clip_norm: float, model) -> None:
    from repro.optim.optimizers import apply_updates, clip_by_global_norm
    from repro.train.trainer import TrainState, make_grad_fn

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    devices = plan.mesh.devices.size
    if fault == "no_exchange" and devices < 2:
        raise ValueError("no_exchange needs a mesh of more than one device")
    grads_of = make_grad_fn(cfg, plan)

    def step(state, batch, lr_scale, rng):
        if fault == "half_batch":
            batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        loss, extras, grads = grads_of(state.params, batch, rng)
        if fault == "no_exchange":
            share = {k: v[: v.shape[0] // devices] for k, v in batch.items()}
            _, ex, g = grads_of(state.params, share, rng)
            scale = ex["denom"] / extras["denom"]
            for k in model.HEAD_GROUPS:
                grads[k] = jax.tree.map(lambda x: x * scale, g[k])
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params, lr_scale)
        metrics = {"loss": loss, "grad_norm": gnorm, "tokens": extras["denom"]}
        if fault == "unchanged":
            return state, metrics
        return TrainState(params=apply_updates(state.params, updates), opt_state=opt_state,
                          scaling=state.scaling), metrics

    sh = trainer._patched_shard()
    trainer.step_fn = jax.jit(step, in_shardings=(sh, None, None, None), out_shardings=(sh, None),
                              donate_argnums=(0,))

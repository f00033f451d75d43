"""A training cell: the program's train step driven through the
repository's ``Trainer``, its first steps checked against the plain
reference, then the timed window.

The check, run in set-up on the same ``Trainer`` object the window then
drives, through its own ``run`` and its own feed:

- ``loss_gap``: the worst relative gap, over the ``CHECK_STEPS`` first
  steps, between the program's loss and the reference's;
- ``grad_norm_gap``: the first step's gradient as the optimizer got it
  (clipped; read back from Adam's first moment, m1 = (1 - b1) g), leaf by
  leaf: the gap between the program's and the reference's norm, over the
  reference's norm of that leaf or of the median leaf, whichever is
  larger; the worst leaf;
- ``update_norm_gap``: the same for each leaf's change after the
  ``CHECK_STEPS`` steps, read before the next step overwrites them.
  Leaves whose reference gradient is under ``STILL_LEAF`` of the median
  leaf's move under Adam by rounding alone, and are left out.

The reference follows the same steps on the same batches from the same
seed: its own weights, gradients in float32 at the matmul precision the
configuration states, global-norm clipping and Adam, as the configuration
states them.  The control is the program under the nearest matmul
precision below the configuration's (``CONTROL_PRECISION``), or the
reference at that precision put in its place (``study.py``).
"""
from __future__ import annotations

import gc
import queue
import threading
import time
from statistics import median

import jax
import jax.numpy as jnp
import numpy as np

CHECK_STEPS = 3
# how much of a traced run's window runs under the profiler: long enough to
# hold a whole block of the traffic, short enough that reading the trace
# stays a fraction of the run
TRACE_SECONDS = 6.0
STILL_LEAF = 1e-3
NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap")
# the nearest matmul precision below the one a configuration states
CONTROL_PRECISION = {"highest": "high"}


def seed_key(seed: int):
    """A JAX key for any whole seed up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)])


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([jnp.linalg.norm(x - y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def leaf_norms(tree, other=None) -> dict[str, float]:
    vals = np.asarray(_norms(tree) if other is None else _diff_norms(tree, other), np.float64)
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(tree)]
    return dict(zip(paths, vals.tolist()))


class Recorder:
    """Wraps the feed: keeps host copies of the first ``keep`` batches, and
    names each fetch in the profiler's trace when ``annotate`` is set.
    Between ``start`` and ``stop`` a thread draws the next ``AHEAD``
    batches while the device works, as an input pipeline does; the batches
    and their order are the feed's either way.  ``waits`` holds, per fetch
    since ``start``, the seconds spent waiting for the batch."""

    AHEAD = 2

    def __init__(self, feed, keep: int):
        self.feed, self.keep, self.kept, self.annotate = feed, keep, [], False
        self._queue, self._thread, self._stop = None, None, None
        self.waits: list[float] = []

    def __iter__(self):
        return self

    def _fill(self):
        while not self._stop.is_set():
            batch = next(self.feed)
            while not self._stop.is_set():
                try:
                    self._queue.put(batch, timeout=0.05)
                    break
                except queue.Full:
                    pass

    def start(self):
        self._queue, self._stop, self.waits = queue.Queue(maxsize=self.AHEAD), threading.Event(), []
        self._thread = threading.Thread(target=self._fill, name="feed", daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join()
        self._thread = None

    def _next(self):
        if self._thread is None:
            return next(self.feed)
        t = time.perf_counter()
        batch = self._queue.get()
        self.waits.append(time.perf_counter() - t)
        return batch

    def __next__(self):
        if self.annotate:
            with jax.profiler.TraceAnnotation("batch_fetch"):
                batch = self._next()
        else:
            batch = self._next()
        if len(self.kept) < self.keep:
            self.kept.append(batch)
        return batch


def build(model, conf: dict, devices, feed, seed: int, *, plant=None):
    """The program's ``Trainer`` for this configuration, its weights made
    on the device from ``seed``, fed by ``feed``; and the init function
    that remakes the first weights.  ``plant`` swaps in a faulty step
    (``faults.plant``)."""
    from repro.train import Trainer

    cfg, plan, opt, clip = model.program(conf, devices)
    plan.validate_batch(feed.feed.spec["pairs_per_step"])
    init, specs = model.program_init(cfg, plan)
    trainer = Trainer(cfg, opt, feed, plan=plan, specs=specs, params=init(seed_key(seed)),
                      clip_norm=clip, seed=seed)
    if plant is not None:
        import faults

        faults.plant(trainer, plant, cfg, plan, opt, clip, model)
    return trainer, init


def program_check(trainer, init, seed: int, b1: float) -> dict:
    """Runs the first ``CHECK_STEPS`` steps through ``trainer.run`` and
    reads what the check compares."""
    quiet = lambda _msg: None  # noqa: E731
    trainer.run(1, log_every=1, log=quiet)
    grads = {k: v / (1.0 - b1) for k, v in leaf_norms(trainer.state.opt_state.m).items()}
    trainer.run(CHECK_STEPS - 1, log_every=1, log=quiet)
    first = init(seed_key(seed))
    moved = leaf_norms(trainer.state.params, first)
    del first
    return {"losses": [h["loss"] for h in trainer.history[-CHECK_STEPS:]], "grad_norms": grads, "update_norms": moved}


def peak_bytes(trainer, batches: list[dict], resident: list[dict], after: list[dict]) -> dict:
    """The fullest device's peak bytes: what was resident there when the
    window began (weights, optimizer moments, the program's buffers) plus
    the most that the compiled step of any of ``batches`` adds while it
    runs (XLA's buffer assignment: temporaries, and outputs that reuse no
    donated argument), or the allocator's own peak where that is higher.
    ``resident`` and ``after`` are each device's ``memory_stats()`` before
    and after the window.  The compiled steps are the ones the window ran,
    looked up, not compiled again."""
    extra = [0]
    for b in batches:
        b = {k: jnp.asarray(v) for k, v in b.items()}
        m = trainer.step_fn.lower(trainer.state, b, trainer.lr_scale, trainer.rng).compile().memory_analysis()
        if m is not None:
            extra.append(m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes)
    res = max(s.get("bytes_in_use", 0) for s in resident)
    alloc = max(s.get("peak_bytes_in_use", 0) for s in after)
    return {"resident": res, "step_adds": max(extra), "allocator_peak": alloc, "peak": max(alloc, res + max(extra))}


def reference_check(model, conf: dict, seed: int, batches: list[dict]) -> dict:
    """The reference's ``CHECK_STEPS`` steps on ``batches``: float32 at the
    configuration's matmul precision, clipping and Adam as it states."""
    o = conf["optimizer"]
    params = reference_params(model, conf, seed_key(seed))
    first = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches[:CHECK_STEPS], start=1):
        loss, g = model.reference_grads(conf, params, batch)
        gn = float(jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))))
        g = jax.tree.map(lambda x: x * min(1.0, o["clip_norm"] / max(gn, 1e-9)), g)
        if t == 1:
            grad_norms = leaf_norms(g)
        params, m, v = _adam(params, g, m, v, t, o["lr"], o["b1"], o["b2"], o["eps"])
        losses.append(loss)
    out = {"losses": losses, "grad_norms": grad_norms, "update_norms": leaf_norms(params, first)}
    del params, first, m, v
    gc.collect()
    return out


def reference_params(model, conf: dict, key):
    """The reference's weights from ``key``, made in one jitted call."""
    return jax.jit(model.reference_init, static_argnums=0)(_Frozen(conf), key)


class _Frozen(dict):
    """A configuration usable as a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


@jax.jit
def _adam(p, g, m, v, t, lr, b1, b2, eps):
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    p = jax.tree.map(lambda x, a, b: x - lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps), p, m, v)
    return p, m, v


def _worst(prog: dict, ref: dict, leaves) -> tuple[float, str]:
    floor = median(ref.values())
    worst, where = 0.0, ""
    for leaf in leaves:
        gap = abs(prog[leaf] - ref[leaf]) / max(ref[leaf], floor)
        if not gap <= worst:  # a NaN is the worst
            worst, where = gap, leaf
    return worst, where


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers and, for the two norm gaps, the worst leaf."""
    loss = max(abs(a - b) / abs(b) if np.isfinite(a) else float("inf") for a, b in zip(prog["losses"], ref["losses"]))
    grad, grad_leaf = _worst(prog["grad_norms"], ref["grad_norms"], ref["grad_norms"])
    cut = STILL_LEAF * median(ref["grad_norms"].values())
    moving = [k for k, g in ref["grad_norms"].items() if g >= cut]
    upd, upd_leaf = _worst(prog["update_norms"], ref["update_norms"], moving)
    return {"loss_gap": loss, "grad_norm_gap": grad, "grad_norm_leaf": grad_leaf,
            "update_norm_gap": upd, "update_norm_leaf": upd_leaf,
            "still_leaves": sorted(set(ref["grad_norms"]) - set(moving))}


def window(trainer, feed: Recorder, seconds: float, trace_dir: str | None = None,
           trace_seconds: float = TRACE_SECONDS) -> dict:
    """Steps ``trainer.run(1)`` until ``seconds`` have passed; the window
    ends when the state after the last step is ready.  With ``trace_dir``
    the first ``trace_seconds`` of it run under the profiler, each call a
    ``train_step`` span and each fetch a ``batch_fetch`` span.  Returns the
    window's length, its steps, the feed history range it consumed, each
    step's host time, and the traced span's steps and seconds."""
    first = len(feed.feed.history)
    feed.start()
    tracing = trace_dir is not None
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        feed.annotate = True
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    traced = None
    t0 = now = time.perf_counter()
    marks = [now]
    while now - t0 < seconds:
        if tracing:
            with jax.profiler.StepTraceAnnotation("train_step", step_num=len(marks) - 1):
                trainer.run(1, log_every=1 << 40)
        else:
            trainer.run(1, log_every=1 << 40)
        now = time.perf_counter()
        marks.append(now)
        if tracing and now - t0 >= trace_seconds:
            traced, tracing = {"steps": len(marks) - 1, "seconds": now - t0}, False
            feed.annotate = False
            jax.profiler.stop_trace()
            traced["stop_s"] = time.perf_counter() - now
            now = time.perf_counter()
    jax.block_until_ready(trainer.state)
    end = time.perf_counter()
    feed.stop()
    if tracing:
        traced = {"steps": len(marks) - 1, "seconds": end - t0}
        feed.annotate = False
        jax.profiler.stop_trace()
        traced["stop_s"] = time.perf_counter() - end
    steps = len(marks) - 1
    return {"seconds": end - t0, "steps": steps, "batches": (first, first + steps), "step_s": np.diff(marks),
            "traced": traced}

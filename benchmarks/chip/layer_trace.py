"""One run of a benchmark cell, read by layer scope and by trainer phase.

    python3 benchmarks/chip/layer_trace.py --workload <name> --seed <n> [--seconds 12] [--trace-seconds 6]

Run from the root of a checkout, on the chip.  Set-up as ``bench.py``
does it: the cell's ``Trainer``, its weights from the seed, the first
``training.CHECK_STEPS`` steps (one batch of each bucket, which compiles
every shape).  Then two windows through ``training.window``: ``--seconds``
untraced, then ``--trace-seconds`` under the profiler.  After them each
bucket's compiled step is looked up (as ``training.peak_bytes`` does) for
its text, and the trace is read twice: by ``trace_reduce.summarize``, as
the benchmark reads it, and by ``scope_reduce.reduce``.  No reference
check: ``bench.py`` decides ``correct``.

One JSON line on standard output:

- ``train_tok_s``: non-pad target tokens per second of the ``untraced``
  and the ``traced`` window, so their ratio is the cost of tracing;
- ``per_layer``: the cell's per-layer metrics of the traced window as
  ``bench.py --trace 1`` computes them;
- ``layers``: ``scope_reduce.metrics``, device microseconds per target
  token by scope and device 0's idle milliseconds per step by phase;
- ``scopes``: per device, seconds by scope, ``other``, forward and
  backward, and busy seconds by both reductions;
- ``idle_s`` by phase, and the longest ``idle_gaps`` named by phase;
- ``scoped_instructions``: per bucket's text, the instructions with a
  layer scope (none means the texts are not this source's).
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def compiled_texts(trainer, batches: list[dict]) -> list[str]:
    """``compiled.as_text()`` of the train step for each batch's shape:
    the programs the window ran, looked up, not compiled again."""
    import jax.numpy as jnp

    out = []
    for b in batches:
        b = {k: jnp.asarray(v) for k, v in b.items()}
        out.append(trainer.step_fn.lower(trainer.state, b, trainer.lr_scale, trainer.rng).compile().as_text())
    return out


def tokens(feed, win: dict, steps: int) -> float:
    """Non-pad target tokens of the first ``steps`` steps of a window."""
    lo = win["batches"][0]
    return float(sum(t.sum() for _, _, t in feed.feed.history[lo:lo + steps]))


def run(cell, devices, *, seed: int, seconds: float, trace_seconds: float) -> dict:
    import bench
    import scope_reduce
    import trace_reduce
    import traffic
    import training
    import workload

    log = bench.CompileLog()
    conf, used = cell.config, devices[: cell.chips]
    peak = workload.peak(used[0].device_kind) if used[0].platform == "tpu" else None
    feed = training.Recorder(traffic.PairFeed(cell.traffic, conf["vocab_size"], seed), keep=training.CHECK_STEPS)
    t0, c0, n0 = time.perf_counter(), log.seconds, log.compiles
    with cell.model.program_context(conf):
        trainer, _ = training.build(cell.model, conf, used, feed, seed)
        trainer.run(training.CHECK_STEPS, log_every=1 << 40)
        setup_s, compile_s, compiles = time.perf_counter() - t0, log.seconds - c0, log.compiles - n0
        plain = training.window(trainer, feed, seconds)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-layers-")
        win = training.window(trainer, feed, trace_seconds, trace_dir=trace_dir, trace_seconds=trace_seconds)
        texts = compiled_texts(trainer, feed.kept)
    span = win["traced"]
    history = feed.feed.history[win["batches"][0]:win["batches"][0] + span["steps"]]
    xplane = trace_reduce.find_xplane(trace_dir)
    summary = trace_reduce.summarize(xplane)
    reduction = scope_reduce.reduce(xplane, texts)
    shutil.rmtree(trace_dir, ignore_errors=True)
    traced_tokens = tokens(feed, win, span["steps"])
    bench_run = {
        "setup_s": setup_s,
        "compile": {"seconds": compile_s, "count": compiles},
        "window": {"seconds": span["seconds"], "steps": span["steps"], "tgt_tokens": traced_tokens,
                   "model_flops": sum(cell.model.model_flops(conf, s, t) for _, s, t in history)},
        "chips": cell.chips,
        "peak": peak,
        "trace": summary,
    }
    per_layer = {name: reader.read(bench_run) for name, (_, reader) in cell.per_layer.items()}
    for d, old in zip(reduction["devices"], summary["devices"]):
        d["busy_s.trace_reduce"] = old["busy_s"]
    return {
        "workload": cell.name,
        "seed": seed,
        "train_tok_s": {"untraced": tokens(feed, plain, plain["steps"]) / plain["seconds"],
                        "traced": traced_tokens / span["seconds"]},
        "steps": {"untraced": plain["steps"], "traced": span["steps"], "traced_in_trace": reduction["steps"]},
        "per_layer": per_layer,
        "layers": scope_reduce.metrics(reduction, traced_tokens),
        "scopes": reduction["devices"],
        "idle_s": reduction["idle_s"],
        "idle_gaps": reduction["idle_gaps"],
        "scoped_instructions": [sum(v[2] is not None for k, v in scope_reduce.parse_module(t).items() if k)
                                for t in texts],
        "top_ops": summary["top_ops"],
        "window_s": reduction["window_s"],
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--trace-seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(CHECKOUT / "src"))
    import bench
    import workload

    cell = workload.load(CHECKOUT, args.workload)
    devices = bench.require_tpu(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the cache's key leaves metadata out by default, so it would hand back a
    # program compiled from other source with the same HLO (another commit's,
    # another checkout's), whose op names, and so scopes, are that source's
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    print(json.dumps(run(cell, devices, seed=args.seed, seconds=args.seconds, trace_seconds=args.trace_seconds)),
          flush=True)


if __name__ == "__main__":
    main()

"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/bench.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic,
check limits and metrics are found by name from ``BENCHMARK.json``
(``workload.py``).  One process:

1. re-executes itself with ``PYTHONHASHSEED=0`` before JAX is imported,
   so that one seed gives the same weights in every process (the
   program's initializer keys each parameter by its path's hash);
2. exits non-zero, printing no result, where JAX finds no TPU or fewer
   chips than the cell asks for, or the checkout holds no program;
3. turns on the program's persistent compilation cache
   (``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set);
4. set-up, under the matmul precision the configuration states: builds
   the program's ``Trainer`` with its weights made on the device from the
   seed, and runs the first ``training.CHECK_STEPS`` steps through it, one
   batch of each bucket shape, which compiles every shape the window uses
   and gives the numbers the check compares;
5. the window: ``Trainer.run(1)`` again and again for ``--seconds``, the
   feed drawing each batch on the host as it is asked for; with
   ``--trace 1`` its first ``training.TRACE_SECONDS`` under the profiler,
   each call a ``train_step`` span and each fetch a ``batch_fetch`` span,
   and the per-layer metrics read that traced span;
6. reads the peak memory (``training.peak_bytes``), frees the program,
   runs the plain reference over the checked steps and compares
   (``training.compare``);
7. prints the numbers compared beside their limits as the last lines of
   standard error, and one JSON line last on standard output: ``correct``,
   ``attempted`` (steps run), ``failed`` (numbers over their limit),
   ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
   per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
   ``checks``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(HERE))

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileLog:
    """Seconds and count of JAX's tracing, lowering and compiling (or cache
    loading) since the process started; one listener per process."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            import jax

            cls._instance = self = super().__new__(cls)
            self.seconds, self.compiles = 0.0, 0
            jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return cls._instance

    def _on_event(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += event == COMPILE_EVENTS[-1]


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def require_tpu(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found {devices[0].platform!r} ({devices[0].device_kind}), not a TPU; the benchmark runs on the chip only")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def run_cell(cell, devices, *, seed: int, seconds: float, trace: bool, t0: float,
             plant: str | None = None) -> dict:
    """Set-up, window, check and metrics of one run; returns the result."""
    import numpy as np

    import traffic
    import training
    import trace_reduce
    import workload

    log = CompileLog()
    conf, used = cell.config, devices[: cell.chips]
    peak = workload.peak(used[0].device_kind) if used[0].platform == "tpu" else None
    feed = training.Recorder(traffic.PairFeed(cell.traffic, conf["vocab_size"], seed), keep=training.CHECK_STEPS)
    c0, phases = log.seconds, {"start": time.perf_counter() - t0}
    with cell.model.program_context(conf):
        trainer, init = training.build(cell.model, conf, used, feed, seed, plant=plant)
        phases["build"] = time.perf_counter() - t0
        prog = training.program_check(trainer, init, seed, conf["optimizer"]["b1"])
        compile_s, compiles = log.seconds - c0, log.compiles
        setup_s = phases["checked_steps"] = time.perf_counter() - t0
        print("set-up seconds since process start:", " ".join(f"{k} {v:.2f}" for k, v in phases.items()),
              f"(compiling or loading programs {compile_s:.2f}, {compiles} programs)", file=sys.stderr)

        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
        # the objects of set-up (JAX's among them) leave the cyclic collector's
        # view, so that a collection inside the window does not walk them all
        gc.collect()
        gc.freeze()
        resident = [d.memory_stats() or {} for d in used]
        win = training.window(trainer, feed, seconds, trace_dir=trace_dir)
        gc.unfreeze()
        window_compiles = log.compiles - compiles
        q = np.percentile(win["step_s"], [0, 10, 50, 90, 99, 100]) if win["steps"] else []
        print("window step seconds (host clock) min/p10/p50/p90/p99/max", " ".join(f"{x:.4f}" for x in q),
              "over", win["steps"], "steps", file=sys.stderr)
        if win["steps"]:
            i = int(np.argmax(win["step_s"]))
            print(f"longest step: {i} at {float(np.sum(win['step_s'][:i])):.2f} s into the window, "
                  f"{win['step_s'][i]:.4f} s, {feed.waits[i]:.4f} s of it waiting for its batch", file=sys.stderr)
        memory = training.peak_bytes(trainer, feed.kept, resident, [d.memory_stats() or {} for d in used])
    print("memory bytes:", " ".join(f"{k} {v}" for k, v in memory.items()), file=sys.stderr)
    peak_bytes = memory["peak"]
    attempted = len(trainer.history) + win["steps"]
    del trainer, init
    gc.collect()

    ref = training.reference_check(cell.model, conf, seed, feed.kept)
    numbers = training.compare(prog, ref)
    # a traced run's per-layer metrics read the traced span of the window
    span = win["traced"] or win
    lo = win["batches"][0]
    history = feed.feed.history[lo:lo + span["steps"]]
    run = {
        "setup_s": setup_s,
        "compile": {"seconds": compile_s, "count": compiles},
        "window": {
            "seconds": span["seconds"],
            "steps": span["steps"],
            "tgt_tokens": float(sum(t.sum() for _, _, t in history)),
            "model_flops": sum(cell.model.model_flops(conf, s, t) for _, s, t in history),
        },
        "chips": cell.chips,
        "peak": peak,
        "trace": None,
    }
    if trace:
        t1 = time.perf_counter()
        run["trace"] = trace_reduce.summarize(trace_reduce.find_xplane(trace_dir))
        print(f"trace of {span['steps']} steps over {span['seconds']:.2f} s: stopped in {span['stop_s']:.1f} s, "
              f"read in {time.perf_counter() - t1:.1f} s", file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)
    readers = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for name, (entry, reader) in readers.items():
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}
    checks = {n: {"value": numbers[n], "limit": cell.limits[n]} for n in training.NUMBERS}
    checks["window_compiles"] = {"value": window_compiles, "limit": 0}
    failed = sum(not c["value"] <= c["limit"] for c in checks.values())
    d0 = used[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": failed == 0, "attempted": attempted, "failed": failed, "metrics": metrics, "device": device}
    if trace:
        t = run["trace"]
        device["busy_s"] = sum(d["busy_s"] for d in t["devices"]) / max(len(t["devices"]), 1)
        device["window_s"] = t["window_s"]
        result["breakdown"] = {"device_ops": t["top_ops"], "idle_gaps": t["idle_gaps"]}
    result["worst_leaves"] = {"grad_norm_gap": numbers["grad_norm_leaf"], "update_norm_gap": numbers["update_norm_leaf"]}
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.execve(sys.executable, [sys.executable, str(Path(__file__).resolve()), *sys.argv[1:]],
                  {**os.environ, "PYTHONHASHSEED": "0"})
    t0 = time.perf_counter()
    if not (CHECKOUT / "src" / "repro").is_dir():
        fail(f"no program at {CHECKOUT / 'src' / 'repro'}: run from a checkout of the repository")
    sys.path.insert(0, str(CHECKOUT / "src"))
    import workload

    cell = workload.load(CHECKOUT, args.workload)
    t_load = time.perf_counter() - t0
    devices = require_tpu(cell.chips)
    print(f"set-up seconds since process start: imports {t_load:.2f} TPU runtime {time.perf_counter() - t0:.2f}",
          file=sys.stderr)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, devices, seed=args.seed, seconds=args.seconds, trace=bool(args.trace), t0=t0)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

"""Readings that the limits of a cell's check are set from, on the chip:
the program, the control and the planted faults, each on many seeds, in
one process, at the cell's own size.  Training readings need no window,
so none is run.  Never part of a benchmark run.

    python3 benchmarks/chip/study.py --workload <name> --seeds 11,12,13 \\
        --variants program,control,half_batch --out <file.jsonl>

Each line of ``--out`` (and of standard output) is one reading:
``{"variant", "seed", "loss_gap", "grad_norm_gap", "update_norm_gap",
"grad_norm_leaf", "update_norm_leaf", "losses", "ref_losses"}``.
``program`` runs the configuration as it states; ``control`` runs the
program under the nearest matmul precision below the configuration's
(``training.CONTROL_PRECISION``); ``reference_control`` puts the
reference, at that lower precision, in the program's place (the control
a CPU can run, ``tests/benchmark``); the others plant a fault
(``faults.py``).  Each program variant's
``Trainer`` is built once and its state remade per seed, so every seed
runs the same compiled step; the reference runs once per seed, with no
program state alive.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.execve(sys.executable, [sys.executable, str(Path(__file__).resolve()), *sys.argv[1:]],
                  {**os.environ, "PYTHONHASHSEED": "0"})
    import jax

    import traffic
    import training
    import workload
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train.trainer import init_train_state

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = workload.load(CHECKOUT, args.workload)
    devices = jax.devices()[: cell.chips]
    conf = cell.config
    seeds = [int(s) for s in args.seeds.split(",")]
    opt = cell.model.program(conf, devices)[2]
    refs, kept = {}, {}
    out = open(args.out, "a")
    lower = dict(conf, matmul_precision=training.CONTROL_PRECISION[conf["matmul_precision"]])
    for variant in args.variants.split(","):
        trainer = init = None
        for seed in seeds:
            t0 = time.perf_counter()
            feed = training.Recorder(traffic.PairFeed(cell.traffic, conf["vocab_size"], seed), keep=training.CHECK_STEPS)
            if variant == "reference_control":
                feed.kept = [next(feed.feed) for _ in range(training.CHECK_STEPS)]
                prog = training.reference_check(cell.model, lower, seed, feed.kept)
            else:
                with cell.model.program_context(lower if variant == "control" else conf):
                    if trainer is None:
                        trainer, init = training.build(
                            cell.model, conf, devices, feed, seed,
                            plant=None if variant in ("program", "control") else variant,
                        )
                    else:
                        # the same compiled step, state made afresh as Trainer does
                        state = init_train_state(init(training.seed_key(seed)), opt)
                        trainer.state = jax.device_put(state, trainer.sshard)
                        trainer.train_iter, trainer.history, trainer.rng = feed, [], jax.random.key(seed)
                    prog = training.program_check(trainer, init, seed, conf["optimizer"]["b1"])
                trainer.state = None
            kept[seed] = feed.kept
            t1 = time.perf_counter()
            if seed not in refs:
                refs[seed] = training.reference_check(cell.model, conf, seed, kept[seed])
            nums = training.compare(prog, refs[seed])
            rec = {"workload": cell.name, "variant": variant, "seed": seed,
                   **{k: nums[k] for k in ("loss_gap", "grad_norm_gap", "update_norm_gap", "grad_norm_leaf",
                                           "update_norm_leaf", "still_leaves")},
                   "losses": prog["losses"], "ref_losses": refs[seed]["losses"],
                   "program_s": t1 - t0, "reference_s": time.perf_counter() - t1}
            line = json.dumps(rec)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
        del trainer


if __name__ == "__main__":
    main()

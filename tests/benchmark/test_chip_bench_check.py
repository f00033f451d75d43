"""The check that decides ``correct``, driven through a whole run of the
harness (the look for a chip skipped) at a size a CPU holds: the program
passes, and each fault a training cell can have, planted under the timed
path, fails, as does the control of the cells' matmul precision.  The
limits are the cells' own."""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

SMOKE = dict(num_layers=2, hidden_size=64, emb_size=32, vocab_size=128)
SEED = 2**31 + 4242


def tiny_run(name: str, model_axis: int = 1, config: dict | None = None, **kw) -> dict:
    """One run of cell ``name`` at smoke widths and 8 pairs a step, its
    wavefront over ``model_axis`` devices, ``config`` overriding keys of
    its configuration."""
    import jax

    import bench
    import workload

    cell = workload.load(ROOT, name)
    cell.config.update(SMOKE, num_layers=max(2, model_axis), **(config or {}))  # a layer per stage
    cell.config["layout"]["mesh"]["model"] = cell.chips = model_axis
    cell.traffic["pairs_per_step"] = 8
    devices = jax.devices()
    assert len(devices) >= cell.chips
    return bench.run_cell(cell, devices, seed=SEED, seconds=0.3, trace=False, t0=time.perf_counter(), **kw)


@functools.lru_cache(maxsize=None)
def program_run(name: str) -> dict:
    return tiny_run(name)


@pytest.mark.parametrize("cell", ["hnmt-wmt-b896-1chip", "hnmtif-wmt-b224-1chip"])
def test_program_passes_and_faults_fail(cell):
    ok = program_run(cell)
    assert ok["correct"], ok["checks"]
    assert ok["metrics"]["train_tok_s"]["value"] > 0 and ok["attempted"] > 3
    for fault in ("unchanged", "half_batch"):
        bad = tiny_run(cell, plant=fault)
        assert not bad["correct"], (fault, bad["checks"])


@pytest.mark.parametrize("cell", ["hnmt-wmt-b896-1chip", "hnmtif-wmt-b224-1chip"])
def test_bfloat16_control_stands_far_above_the_program_where_float32_is_exact(cell):
    """The program's own bfloat16 path against its float32: on the CPU a
    float32 matmul is exact to rounding, and the check sees the lower
    precision at once."""
    ok, low = program_run(cell), tiny_run(cell, config={"compute_dtype": "bfloat16"})
    for n in ("grad_norm_gap", "update_norm_gap"):
        assert low["checks"][n]["value"] > 30 * ok["checks"][n]["value"], (n, low["checks"], ok["checks"])


@pytest.mark.parametrize("cell", ["hnmt-wmt-b896-1chip", "hnmtif-wmt-b224-1chip"])
def test_the_control_fails_the_check(cell):
    """The control of a ``highest`` configuration: the reference at the
    MXU's three-pass precision, put in the program's place, against the
    reference, on the checked steps of a run.  (On the chip the program
    itself under ``high`` is the control; a CPU multiplies float32 in full
    whatever the precision asked for.)"""
    import traffic
    import training
    import workload

    c = workload.load(ROOT, cell)
    conf = c.config | SMOKE
    feed = traffic.PairFeed(c.traffic | {"pairs_per_step": 8}, conf["vocab_size"], SEED)
    kept = [next(feed) for _ in range(training.CHECK_STEPS)]
    ref = training.reference_check(c.model, conf, SEED, kept)
    lower = conf | {"matmul_precision": training.CONTROL_PRECISION[conf["matmul_precision"]]}
    nums = training.compare(training.reference_check(c.model, lower, SEED, kept), ref)
    assert any(nums[n] > c.limits[n] for n in training.NUMBERS), nums


SHARDED = f"""
import json, sys
sys.path[:0] = [{str(CHIP)!r}, {str(ROOT / 'src')!r}, {str(Path(__file__).parent)!r}]
from test_chip_bench_check import tiny_run
print(json.dumps({{p or "program": tiny_run("hnmt-wmt-b896-1chip", model_axis=4, plant=p)["correct"]
                  for p in (None, "no_exchange", "half_batch")}}))
"""


def test_four_device_layout_catches_a_missing_exchange():
    """The paper's hybrid on (data=1, model=4), as the four-chip cell that
    PERF.md keeps for later would run it, on four host devices."""
    env = {k: v for k, v in os.environ.items()} | {
        "PYTHONHASHSEED": "0", "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", SHARDED], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {"program": True, "no_exchange": False, "half_batch": False}


def test_three_pass_product_is_the_mxus_high_precision():
    """The reference's ``high``, the control of a ``highest``
    configuration: bfloat16 rounding to the bit, an error between float32's
    and bfloat16's, and gradients that are three-pass products too."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import workload

    m = workload.load_module(CHIP / "models" / "seq2seq_lstm.py", "three_pass_model")
    x = jax.random.normal(jax.random.key(0), (4096,)) * 100
    cut = jax.jit(m._bf16)(x)
    assert jnp.all(jnp.abs(cut) <= jnp.abs(x)) and jnp.all(jnp.abs(x - cut) < jnp.abs(x) * 2.0**-7)
    assert jnp.array_equal(cut, cut.astype(jnp.bfloat16).astype(jnp.float32))
    a, b = (jax.random.normal(jax.random.key(i), (32, 512)) for i in (1, 2))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64).T
    err = {p: np.abs(np.asarray(m._mm("ik,jk->ij", a, b, p), np.float64) - exact).max() for p in ("highest", "high")}
    one_pass = np.abs(np.asarray(m._bf16(a), np.float64) @ np.asarray(m._bf16(b), np.float64).T - exact).max()
    assert 3 * err["highest"] < err["high"] < one_pass / 30, (err, one_pass)
    grads = {p: jax.grad(lambda a_: (m._mm("ik,jk->ij", a_, b, p) ** 2).sum())(a) for p in ("highest", "high")}
    gap = float(jnp.abs(grads["high"] - grads["highest"]).max() / jnp.abs(grads["highest"]).max())
    assert 0 < gap < 1e-4, gap

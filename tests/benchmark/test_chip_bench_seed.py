"""One seed gives the same weights in every benchmark process, and the
plain reference draws the very weights the program does."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
SMOKE = dict(num_layers=2, hidden_size=32, emb_size=16, vocab_size=64)

INIT = f"""
import json, sys
import numpy as np
sys.path[:0] = [{str(CHIP)!r}, {str(ROOT / "src")!r}]
import jax, training, workload
conf = json.loads(open({str(CHIP / "configs" / "seq2seq-rnn.json")!r}).read()) | {SMOKE!r}
model = workload.load_module(workload.HERE / "models" / "seq2seq_lstm.py", "m")
cfg, plan, _, _ = model.program(conf, jax.devices())
init, _ = model.program_init(cfg, plan)
leaves = jax.tree.leaves(init(training.seed_key(2**31 + 5)))
print(json.dumps([float(np.asarray(x, np.float64).sum()) for x in leaves]))
"""


def _checksums():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"} | {"PYTHONHASHSEED": "0", "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", INIT], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_two_processes_draw_the_same_weights_from_one_seed():
    a, b = _checksums(), _checksums()
    assert a == b and len(a) == 2 + 2 * 2 * 3 + 3


def test_reference_draws_the_programs_weights():
    sys.path[:0] = [str(CHIP), str(ROOT / "src")]
    import jax
    import numpy as np

    import training
    import workload

    conf = json.loads((CHIP / "configs" / "seq2seq-rnn-if.json").read_text()) | SMOKE
    model = workload.load_module(CHIP / "models" / "seq2seq_lstm.py", "seed_model")
    cfg, plan, _, _ = model.program(conf, jax.devices())
    init, _ = model.program_init(cfg, plan)
    key = training.seed_key(2**33 + 17)
    prog, ref = init(key), training.reference_params(model, conf, key)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for p, r in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(r))

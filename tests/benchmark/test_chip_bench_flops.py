"""The benchmark's analytic model FLOPs against the dot FLOPs of the
program's own forward, and its table of chip peaks."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

import workload  # noqa: E402

SMOKE = dict(num_layers=2, hidden_size=48, emb_size=24, vocab_size=96)


def dot_flops(jaxpr) -> float:
    """2 x MACs of every dot_general in a jaxpr, scan bodies times their
    length, nested calls included."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, rc), (lb, _) = eqn.params["dimension_numbers"]
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            contract = np.prod([lhs[d] for d in lc])
            batch = np.prod([lhs[d] for d in lb])
            free_l = np.prod([s for d, s in enumerate(lhs) if d not in lc and d not in lb])
            free_r = np.prod([s for d, s in enumerate(rhs) if d not in rc and d not in eqn.params["dimension_numbers"][1][1]])
            total += 2.0 * batch * free_l * free_r * contract
            continue
        mult = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                if isinstance(sub, ClosedJaxpr):
                    total += mult * dot_flops(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    total += mult * dot_flops(sub)
    return total


@pytest.mark.parametrize("config", ["seq2seq-rnn", "seq2seq-rnn-if"])
def test_analytic_forward_flops_match_the_programs_dots(config):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import seq2seq as s2s

    conf = json.loads((ROOT / "benchmarks" / "chip" / "configs" / f"{config}.json").read_text()) | SMOKE
    model = workload.load_module(ROOT / "benchmarks" / "chip" / "models" / f"{conf['model']}.py", "flops_model")
    cfg = dataclasses.replace(
        get_config("seq2seq-rnn"), num_layers=conf["num_layers"], d_model=conf["hidden_size"],
        emb_size=conf["emb_size"], vocab_size=conf["vocab_size"], input_feeding=conf["input_feeding"],
        dropout=0.0, dtype="float32",
    )
    B, M, N = 3, 7, 5  # every position real
    params, _ = s2s.init_seq2seq(jax.random.key(0), cfg)
    batch = s2s.Seq2SeqBatch(
        src=jnp.ones((B, M), jnp.int32), tgt_in=jnp.ones((B, N), jnp.int32), tgt_out=jnp.ones((B, N), jnp.int32),
        src_mask=jnp.ones((B, M), bool), tgt_mask=jnp.ones((B, N), bool),
    )
    jaxpr = jax.make_jaxpr(lambda p: s2s.forward(p, cfg, batch)[0])(params).jaxpr
    counted = dot_flops(jaxpr)
    analytic = model.model_flops(conf, np.full(B, M), np.full(B, N))
    if conf["input_feeding"]:
        # the input-feeding forward runs the whole head (eq. 1-5) once per
        # step inside the decoder scan and again over all steps after it;
        # model FLOPs count it once
        f = model.flops_per_token(conf)
        e, h = conf["emb_size"], conf["hidden_size"]
        head = f["tgt"] - 2 * (e + h + h) * 4 * h - (conf["num_layers"] - 1) * 2 * (2 * h) * 4 * h
        analytic += B * N * (head + f["tgt_src"] * M)
    assert counted == pytest.approx(analytic, rel=1e-12)


def test_published_widths_give_the_papers_flops_per_pair():
    for config, want in (("seq2seq-rnn", 198e6), ("seq2seq-rnn-if", 206e6)):
        conf = json.loads((ROOT / "benchmarks" / "chip" / "configs" / f"{config}.json").read_text())
        model = workload.load_module(ROOT / "benchmarks" / "chip" / "models" / f"{conf['model']}.py", "flops_model")
        f = model.flops_per_token(conf)
        assert f["src"] + f["tgt"] == pytest.approx(want, rel=0.01)
        assert f["tgt_src"] == 4 * conf["hidden_size"]


def test_peak_table_knows_v5e_and_refuses_unknown_kinds():
    assert workload.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert "Google Cloud" in workload.peak("TPU v5 lite")["source"]
    for kind in ("TPU v9 imaginary", "cpu", "about"):
        with pytest.raises(KeyError):
            workload.peak(kind)

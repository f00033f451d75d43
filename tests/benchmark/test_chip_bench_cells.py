"""The chip benchmark as data: every cell of ``BENCHMARK.json`` resolves its
files by name, a new cell is files and entries only, the traffic is
fixed by its seed, and the harness refuses to run off the chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

import traffic  # noqa: E402
import workload  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files_by_name(cell):
    c = workload.load(ROOT, cell)
    assert c.config["model"] and callable(c.model.program) and callable(c.model.reference_grads)
    assert c.traffic["pairs_per_step"] > 0 and set(c.limits) >= {"loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert {"setup_s", "train_tok_s"} <= set(c.end_to_end)
    assert c.per_layer, "every cell reports a per-layer metric"
    for _, reader in {**c.end_to_end, **c.per_layer}.values():
        assert callable(reader.read)
    mesh = c.config["layout"]["mesh"]
    assert mesh["data"] * mesh["model"] == c.chips


def test_benchmark_json_keeps_the_contracts_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for c in BENCH["configs"]:
        assert c["name"] in {w["config"] for w in BENCH["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A later PR's configuration, traffic mix, cell and metric, added as
    files beside the others and entries in BENCHMARK.json, resolve with no
    edit to the harness."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", chip, ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((chip / "configs" / "seq2seq-rnn.json").read_text())
    conf["layout"]["strategy"] = "data"
    (chip / "configs" / "seq2seq-rnn-data4.json").write_text(json.dumps(conf | {"layout": conf["layout"] | {"mesh": {"data": 4, "model": 1}}}))
    mix = json.loads((chip / "traffic" / "wmt14-ende-b224.json").read_text())
    (chip / "traffic" / "patent-b64.json").write_text(json.dumps(mix | {"pairs_per_step": 64}))
    (chip / "workloads" / "hnmt-patent-data4.json").write_text(json.dumps({"limits": {"loss_gap": 1, "grad_norm_gap": 1, "update_norm_gap": 1}}))
    (chip / "metrics" / "steps_per_s.train.py").write_text("def read(run):\n    return run['window']['steps'] / run['window']['seconds']\n")
    bench["configs"].append({"name": "seq2seq-rnn-data4", "source": "https://arxiv.org/abs/1909.00562",
                             "file": "benchmarks/chip/configs/seq2seq-rnn-data4.json", "reduced": ["dropout"], "why": "x"})
    bench["workloads"].append({"name": "hnmt-patent-data4", "config": "seq2seq-rnn-data4", "traffic": "patent-b64",
                               "chips": 4, "why": "x"})
    bench["per_layer"].append({"name": "steps_per_s.train", "unit": "1/s", "better": "higher", "source": "host_clock",
                               "layer": "train step", "moves": "train_tok_s", "workloads": ["hnmt-patent-data4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = workload.load(tmp_path, "hnmt-patent-data4")
    assert c.chips == 4 and c.traffic["pairs_per_step"] == 64 and c.config["layout"]["strategy"] == "data"
    # the metrics already there list their cells; the new cell reports its own
    assert set(c.per_layer) == {"steps_per_s.train"} and {"setup_s", "train_tok_s"} <= set(c.end_to_end)
    assert c.per_layer["steps_per_s.train"][1].read({"window": {"steps": 10, "seconds": 4.0}}) == 2.5


def test_traffic_is_fixed_by_its_seed_and_keeps_its_block_mix():
    spec = traffic.load(ROOT / "benchmarks" / "chip" / "traffic" / "wmt14-ende-b224.json")
    # the lognormal's mass in each bucket, 0.234 / 0.552 / 0.214 of 15 batches
    assert traffic.block_mix(spec) == [4, 8, 3]
    seed = 2**31 + 977
    a, b, c = (traffic.PairFeed(spec, 32000, s) for s in (seed, seed, seed + 1))
    for _ in range(3 + 2 * 15):
        x, y, z = next(a), next(b), next(c)
        for k in x:
            assert np.array_equal(x[k], y[k])
    assert not all(np.array_equal(x[k], z[k]) for k in x)
    pads = [h[0] for h in a.history]
    assert pads[:3] == [16, 32, 51], "the stream opens with one batch of each shape"
    for block in (pads[3:18], pads[18:33]):
        assert sorted(block) == [16] * 4 + [32] * 8 + [51] * 3
    assert [h[0] for h in c.history][3:18] != pads[3:18]
    for pad, src_len, tgt_len in a.history:
        lo, hi = {16: (1, 15), 32: (16, 31), 51: (32, 50)}[pad]
        assert src_len.min() >= lo and src_len.max() <= hi
        assert np.array_equal(tgt_len, np.maximum(1, np.rint(0.948 * src_len)) + 1)
    src = np.concatenate([h[1] for h in a.history])
    tgt = np.concatenate([h[2] for h in a.history]) - 1
    assert 0.94 < tgt.sum() / src.sum() < 0.955
    batch = x
    L, T = int(a.history[-1][1][0]), int(a.history[-1][2][0]) - 1
    assert batch["src_mask"][0].sum() == L and batch["tgt_mask"][0].sum() == T + 1
    assert batch["tgt_out"][0, T] == traffic.EOS
    assert np.array_equal(batch["tgt_out"][0, :T], traffic.map_tokens(batch["src"][0, :L][::-1], 32000)[:T])
    assert np.array_equal(batch["tgt_in"][0, 1:], batch["tgt_out"][0, :-1]) and batch["tgt_in"][0, 0] == traffic.BOS


def test_traffic_mean_is_the_published_one():
    """Luong et al. 2015: 116M English words in 4.5M WMT14 pairs."""
    spec = traffic.load(ROOT / "benchmarks" / "chip" / "traffic" / "wmt14-ende-b896.json")
    s = spec["src_len"]
    assert s["mean"] == 25.8 and round(116e6 / 4.5e6, 1) == 25.8 and spec["tgt_per_src"] == round(110 / 116, 3)
    rng = np.random.default_rng(0)
    draw = rng.lognormal(np.log(s["mean"]) - s["sigma"] ** 2 / 2, s["sigma"], 200_000)
    assert abs(draw.mean() - 25.8) < 0.1


def _bench(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "XLA_FLAGS")} | env_extra
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_harness_refuses_a_host_without_a_tpu():
    out = _bench(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "not a TPU" in out.stderr


def test_harness_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""

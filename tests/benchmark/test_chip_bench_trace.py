"""The trace reduction, on a small trace recorded on a TPU v5e chip
(``benchmarks/chip/record_trace.py``) and on hand-made intervals."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

import trace_reduce  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / "v5e_1chip.xplane.pb"


def test_interval_arithmetic():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace_reduce.length([(0, 3), (5, 8)]) == 6
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace_reduce.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert trace_reduce.subtract([(2, 3)], [(0, 10)]) == []


@pytest.mark.parametrize("name,want", [
    ("all-reduce.3", True), ("all-reduce-start.1", True), ("all-reduce-done", True),
    ("collective-permute-start.12", True), ("collective-permute-done.2", True), ("all-gather.7", True),
    ("reduce-scatter.1", True), ("all-to-all.4", True), ("%all-reduce.9", True),
    ("fusion.12", False), ("reduce.3", False), ("copy-start.2", False), ("dot.1", False), ("all-reducer", False),
    ("%all-reduce.5 = f32[1024]{0} all-reduce(f32[1024]{0} %p), replica_groups={{0,1,2,3}}", True),
    ("%cp = (f32[8]{0}, f32[8]{0}, u32[]{:S(2)}) collective-permute-start(f32[8]{0} %x), source_target_pairs={{0,1}}", True),
    ("%fusion.3 = f32[512,512]{1,0:T(8,128)} fusion(f32[512,512]{1,0:T(8,128)S(1)} %copy-done), kind=kOutput", False),
    ("%copy-start = (f32[512,512]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(f32[512,512]{1,0:T(8,128)} %a.1)", False),
])
def test_collectives_are_told_by_opcode(name, want):
    assert trace_reduce.is_collective(name) is want


def test_reduction_of_a_recorded_chip_trace():
    t = trace_reduce.summarize(FIXTURE)
    assert len(t["devices"]) == 1
    d = t["devices"][0]
    # five train_step spans, each a 3 ms host sleep then a short step
    assert 0.015 < t["window_s"] < 1.0
    assert 0 < d["busy_s"] < t["window_s"]
    assert d["collective_s"] == 0 == d["exposed_collective_s"]
    assert t["top_ops"] and all(s > 0 for _, s in t["top_ops"])
    assert sum(s for _, s in t["top_ops"]) == pytest.approx(d["busy_s"], rel=0.05)
    labels = [label for label, _ in t["idle_gaps"]]
    assert labels[0] == "batch_fetch" and t["idle_gaps"][0][1] >= 0.0025
    assert set(labels) <= {"batch_fetch", "train_step", "compiling", "host"}

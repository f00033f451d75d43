"""The program's layer scopes and trainer phase spans, and the reduction
that reads them (``scope_reduce.py``): on a small trace recorded on a TPU
v5e chip with the compiled text of its module
(``benchmarks/chip/record_scoped_trace.py``; the trace kept gzipped, the
text without its stack-frame tables, and recorded before the script
compiled the read-back's slice ahead of the trace, so its first step holds
that compile), on compiled train steps of both configurations at small
widths on the CPU (one device, and four host devices under the hybrid
plan), and on hand-made events and intervals.  The old reduction's numbers
on the old trace are pinned here too."""
from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "v5e_1chip.xplane.pb"
SCOPED_GZ = DATA / "v5e_1chip_scoped.xplane.pb.gz"
SCOPED_HLO = DATA / "v5e_1chip_scoped.hlo.txt"
SMOKE = dict(num_layers=2, hidden_size=64, emb_size=32, vocab_size=128)
SEED = 2**31 + 4243
CELLS = ["hnmt-wmt-b896-1chip", "hnmtif-wmt-b224-1chip"]


def test_old_reduction_reads_as_before_on_the_old_trace():
    assert trace_reduce.summarize(OLD) == {
        "window_s": 0.023190937,
        "devices": [{"name": "/device:TPU:0", "busy_s": 2.6837e-05, "collective_s": 0.0,
                     "exposed_collective_s": 0.0}],
        "top_ops": [["fusion", 1.9625000000000003e-05], ["copy-done", 7.145e-06], ["copy-start", 6.7e-08]],
        "idle_gaps": [["batch_fetch", 0.005282623], ["batch_fetch", 0.004570466], ["train_step", 0.004341257],
                      ["batch_fetch", 0.004214468], ["batch_fetch", 0.002588158], ["batch_fetch", 0.002167112],
                      ["batch_fetch", 2e-09], ["batch_fetch", 2e-09], ["batch_fetch", 2e-09], ["batch_fetch", 2e-09]],
    }


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/transpose(jvp(decoder))/while/body/closed_call/dot_general", "decoder"),
    ("jit(train_step)/jvp(decoder)/while/body/closed_call/decoder/jit(_where)/select_n", "decoder"),
    ("jit(train_step)/jvp(head)/bnh,hk,bmk->bnm", "head"),
    ("jit(train_step)/vmap(transpose(jvp(embed)))/scatter-add", "embed"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(train_step)/jvp(encoder)/handoff/slice", "handoff"),
    ("jit(train_step)/jvp(handoff)/sharding_constraint", "handoff"),
    ("jit(train_step)/jit(_where)/broadcast_in_dim", None),
    ("jit(head)/mul", None),
    ("broadcast.89", None),
    ("", None),
])
def test_scope_is_the_outermost_layer_and_handoff_wherever_it_stands(op_name, want):
    assert scope_reduce.scope_of(op_name) == want


@pytest.mark.parametrize("ops,want", [
    ([(0, 10), (2, 4), (5, 6)], [7, 2, 1]),  # a loop and two body ops with events of their own
    ([(0, 4), (2, 6)], [2, 4]),  # overlapping, not nested
    ([(0, 1), (3, 5)], [1, 2]),  # apart
    ([(0, 2), (0, 2)], [0, 2]),  # the same stretch twice
    ([(0, 10), (2, 8), (3, 4)], [4, 5, 1]),  # nested twice
])
def test_own_times_split_the_union_among_the_innermost(ops, want):
    own = scope_reduce.own_times(ops)
    assert own == want
    assert sum(own) == trace_reduce.length(trace_reduce.union(ops))


def test_per_layer_numbers_of_a_reduction():
    red = {"steps": 4, "devices": [{"scopes_s": dict.fromkeys(scope_reduce.SCOPES, 0.0) | {"decoder": 0.5}}],
           "idle_s": dict.fromkeys(scope_reduce.PHASES, 0.002) | {"trainer.sync": 0.004}}
    got = scope_reduce.metrics(red, tgt_tokens=1e4)
    assert got["device_us_per_tok.decoder"] == pytest.approx(50.0)
    assert got["device_us_per_tok.encoder"] == 0.0
    assert got["idle_ms_per_step.sync"] == pytest.approx(1.0) and got["idle_ms_per_step.put"] == pytest.approx(0.5)
    assert len(got) == 8
    assert scope_reduce.metrics(red | {"devices": [], "idle_s": {}}, 1e4) == {}


# Two modules with the same instruction names, as two buckets' steps have:
# A's fusion.1 is the head's backward, B's the decoder's; fusion.9 is B's only.
MODULE = """HloModule jit_train_step, is_scheduled=true

%fused.1 (param_0: f32[{n}]) -> f32[{n}] {{
  ROOT %param_0 = f32[{n}]{{0}} parameter(0), metadata={{op_name="jit(train_step)/{inner}"}}
}}

%body (p: (s32[], f32[{n}])) -> (s32[], f32[{n}]) {{
  %p = (s32[], f32[{n}]{{0}}) parameter(0)
  %fusion.2 = f32[{n}]{{0}} fusion(%p), kind=kLoop, calls=%fused.1, metadata={{op_name="jit(train_step)/jvp(encoder)/while/body/tanh" stack_frame_id=2}}
  ROOT %tuple = (s32[], f32[{n}]{{0}}) tuple(%p, %fusion.2)
}}

ENTRY %main.7 (a: f32[{n}]) -> f32[{n}] {{
  %a = f32[{n}]{{0}} parameter(0)
  %while.1 = (s32[], f32[{n}]{{0}}) while(%a), condition=%cond, body=%body, metadata={{op_name="jit(train_step)/jvp(encoder)/while"}}
  %fusion.1 = f32[{n}]{{0}} fusion(%while.1), kind=kOutput, calls=%fused.1, metadata={{op_name="jit(train_step)/{fusion1}"}}
  {extra}
  ROOT %fusion.3 = f32[{n}]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused.1, metadata={{op_name="jit(train_step)/optimizer/mul"}}
}}
"""
MODULE_A = MODULE.format(n=8, inner="head/x", fusion1="transpose(jvp(head))/dot_general", extra="")
MODULE_B = MODULE.format(n=16, inner="decoder/x", fusion1="jvp(decoder)/dot_general", extra=(
    '%fusion.9 = f32[16]{0} fusion(%a), kind=kLoop, calls=%fused.1, '
    'metadata={op_name="jit(train_step)/transpose(jvp(embed))/scatter-add"}'))


def _op(name: str, s: int, e: int):
    return (s, e, f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %a), kind=kLoop, calls=%fused.1")


def test_reduction_of_hand_made_events_joins_each_run_to_its_module():
    ops = [_op("while.1", 0, 60), _op("fusion.2", 10, 30), _op("fusion.2", 35, 55), _op("fusion.1", 60, 90),
           _op("fusion.3", 90, 100),  # a run of module A
           _op("fusion.1", 200, 260), _op("fusion.9", 260, 300),  # a run of B
           (400, 410, "%threefry = u32[2]{0} fusion(u32[2]{0} %k), kind=kLoop")]  # a module not given
    runs = [(0, 100, "jit_train_step(1)"), (200, 300, "jit_train_step(2)"), (400, 410, "jit__split(3)")]
    spans = [("train_step", 0, 150), ("train_step", 150, 420), ("trainer.fetch", 100, 130),
             ("batch_fetch", 101, 129), ("trainer.put", 130, 160), ("trainer.dispatch", 160, 210),
             ("trainer.sync", 210, 305)]
    red = scope_reduce.reduce_events(spans, [("/device:TPU:0", ops, runs)], [MODULE_A, MODULE_B])
    (d,) = red["devices"]
    ns = 1e-9
    want = dict.fromkeys(scope_reduce.SCOPES, 0.0) | {"encoder": 60 * ns, "head": 30 * ns, "optimizer": 10 * ns,
                                                      "decoder": 60 * ns, "embed": 40 * ns}
    assert d["scopes_s"] == pytest.approx(want)
    assert d["other_s"] == pytest.approx(10 * ns) and d["busy_s"] == pytest.approx(210 * ns)
    assert d["forward_s"] == pytest.approx(120 * ns) and d["backward_s"] == pytest.approx(70 * ns)
    assert red["steps"] == 2 and red["window_s"] == pytest.approx(420 * ns)
    assert red["idle_s"] == pytest.approx({"trainer.fetch": 30 * ns, "trainer.put": 30 * ns,
                                           "trainer.dispatch": 40 * ns, "trainer.sync": 5 * ns, "outside": 105 * ns})
    assert red["idle_gaps"] == [["trainer.put", pytest.approx(100 * ns)], ["train_step", pytest.approx(100 * ns)],
                                ["train_step", pytest.approx(10 * ns)]]
    assert scope_reduce.metrics(red, tgt_tokens=10)["device_us_per_tok.decoder"] == pytest.approx(60e-3 / 10)


# --- the recorded scoped trace --------------------------------------------


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """The recorded scoped trace, unpacked (it is kept gzipped)."""
    path = tmp_path_factory.mktemp("scoped") / "v5e_1chip_scoped.xplane.pb"
    path.write_bytes(gzip.decompress(SCOPED_GZ.read_bytes()))
    return path


def test_scopes_of_the_recorded_module():
    mod = scope_reduce.parse_module(SCOPED_HLO.read_text())
    sched = mod.pop(None)
    scoped = {scope for opcode, op, scope, comp in mod.values()
              if comp in sched and scope_reduce.WORK.match(opcode) and op.startswith("jit(")}
    assert scoped == {"encoder", "head"}
    assert any(opcode == "while" and scope == "encoder" for opcode, _, scope, _ in mod.values())


def test_scopes_and_other_add_up_to_the_busy_time(scoped):
    old = trace_reduce.summarize(scoped)
    red = scope_reduce.reduce(scoped, [SCOPED_HLO.read_text()])
    assert red["steps"] == 5 and red["window_s"] == old["window_s"]
    (d,), (o,) = red["devices"], old["devices"]
    assert d["busy_s"] == pytest.approx(o["busy_s"], rel=0.02)
    assert d["scopes_s"]["encoder"] > 0 and d["scopes_s"]["head"] > 0
    assert sum(d["scopes_s"].values()) + d["other_s"] == pytest.approx(d["busy_s"])
    assert d["forward_s"] == pytest.approx(d["scopes_s"]["encoder"] + d["scopes_s"]["head"])
    assert d["backward_s"] == 0.0
    # the read-back of one element is a module of its own, not given: other
    assert 0 < d["other_s"] < 0.5 * d["busy_s"]


def test_an_unmatched_event_goes_to_other(scoped):
    text = SCOPED_HLO.read_text()
    every = scope_reduce.reduce(scoped, [])["devices"][0]
    assert every["other_s"] == every["busy_s"] > 0 and not any(every["scopes_s"].values())
    full = scope_reduce.reduce(scoped, [text])["devices"][0]
    mod = scope_reduce.parse_module(text)
    mod.pop(None)
    head = [n for n, (opc, _, scope, _) in mod.items() if scope == "head" and opc == "fusion"]
    renamed = text
    for n in head:
        renamed = renamed.replace(f"%{n} ", f"%gone.{n} ")
    less = scope_reduce.reduce(scoped, [renamed])["devices"][0]
    assert less["scopes_s"]["head"] < full["scopes_s"]["head"]
    moved = full["scopes_s"]["head"] - less["scopes_s"]["head"]
    assert less["other_s"] == pytest.approx(full["other_s"] + moved)


def test_idle_time_by_phase_is_the_interval_arithmetic(scoped):
    """Each phase's idle time, from its spans and device 0's operations
    directly: the span's length less the union of operations inside it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(scoped))
    spans, ops = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name.startswith("/host:"):
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
                elif plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    steps = [(s, e) for n, s, e in spans if n == "train_step"]
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    red = scope_reduce.reduce(scoped, [SCOPED_HLO.read_text()])
    total = 0.0
    for phase in scope_reduce.PHASES:
        want = 0.0
        for n, s, e in spans:
            if n == phase:
                s, e = max(s, lo), min(e, hi)
                busy = trace_reduce.union((max(a, s), min(b, e)) for a, b in ops if b > s and a < e)
                want += (e - s - trace_reduce.length(busy)) / 1e9
        assert red["idle_s"][phase] == pytest.approx(want, abs=1e-9), phase
        total += want
    idle = (hi - lo) / 1e9 - red["devices"][0]["busy_s"]
    assert red["idle_s"]["outside"] == pytest.approx(idle - total, abs=1e-9)
    # five 3 ms sleeps in trainer.fetch, each leaving the device idle
    assert red["idle_s"]["trainer.fetch"] >= 5 * 0.0025
    got = scope_reduce.metrics(red, tgt_tokens=1.0)
    assert got["idle_ms_per_step.fetch"] == pytest.approx(1e3 * red["idle_s"]["trainer.fetch"] / 5)


def test_gaps_are_named_by_phase_and_the_old_names_stay(scoped):
    red = scope_reduce.reduce(scoped, [SCOPED_HLO.read_text()])
    labels = {label for label, _ in red["idle_gaps"]}
    assert "batch_fetch" in labels and labels <= {"batch_fetch", "train_step", "host", *scope_reduce.PHASES}
    old = {label for label, _ in trace_reduce.summarize(scoped)["idle_gaps"]}
    assert old <= {"batch_fetch", "train_step", "compiling", "host"}


# --- the program's scopes, compiled on the CPU ------------------------------


def _small_trainer(name: str, devices, model_axis: int = 1, pairs: int = 8):
    import traffic
    import training
    import workload

    cell = workload.load(ROOT, name)
    cell.config.update(SMOKE, num_layers=max(2, model_axis))
    cell.config["layout"]["mesh"]["model"] = model_axis
    cell.traffic["pairs_per_step"] = pairs
    feed = training.Recorder(traffic.PairFeed(cell.traffic, cell.config["vocab_size"], SEED), keep=3)
    trainer, _ = training.build(cell.model, cell.config, devices[:model_axis], feed, SEED)
    return cell, trainer, feed


def _program_work_without_scope(text: str) -> list:
    """Work instructions whose ``op_name`` comes from the program (a path
    under the jitted step) and names no layer.  Instructions the compiler
    makes (buffer allocations, one-op wrappers) carry no such path."""
    return [w for w in scope_reduce.work_without_scope(text) if w[2].startswith("jit(")]


@pytest.mark.parametrize("cell", CELLS)
def test_every_work_instruction_of_the_step_has_a_layer_scope(cell):
    import jax
    import layer_trace
    import traffic

    c, trainer, _ = _small_trainer(cell, jax.devices())
    feed = traffic.PairFeed(c.traffic, c.config["vocab_size"], SEED)
    with c.model.program_context(c.config):
        texts = layer_trace.compiled_texts(trainer, [next(feed) for _ in range(3)])
    assert len({t.count("\n") for t in texts}) >= 2  # three buckets, at least two programs
    for text in texts:
        assert _program_work_without_scope(text) == []
        mod = scope_reduce.parse_module(text)
        sched = mod.pop(None)
        seen = {s for opc, _, s, comp in mod.values() if comp in sched and scope_reduce.WORK.match(opc)}
        assert {"embed", "encoder", "decoder", "head", "optimizer"} <= seen, seen


FOUR = f"""
import json, sys
sys.path[:0] = [{str(CHIP)!r}, {str(ROOT / 'src')!r}, {str(Path(__file__).parent)!r}]
import jax
import jax.numpy as jnp
import scope_reduce, trace_reduce, traffic
from test_chip_bench_layers import _small_trainer, _program_work_without_scope
c, trainer, _ = _small_trainer("hnmt-wmt-b896-1chip", jax.devices(), model_axis=4)
b = {{k: jnp.asarray(v) for k, v in next(traffic.PairFeed(c.traffic, c.config["vocab_size"], 7)).items()}}
with c.model.program_context(c.config):
    text = trainer.step_fn.lower(trainer.state, b, trainer.lr_scale, trainer.rng).compile().as_text()
mod = scope_reduce.parse_module(text)
sched = mod.pop(None)
coll = [[n, opc, s] for n, (opc, _, s, comp) in mod.items()
        if comp in sched and scope_reduce.WORK.match(opc) and trace_reduce.is_collective(opc)]
print(json.dumps({{"collectives": coll, "unscoped": _program_work_without_scope(text)}}))
"""


def test_four_device_collectives_have_scopes_and_the_reshard_is_the_handoff():
    """The paper's hybrid on (data=1, model=4) on four host devices: every
    collective of the step has a layer scope, and the phase boundary's
    reshard to the batch layout is the hand-off."""
    env = {k: v for k, v in os.environ.items()} | {
        "PYTHONHASHSEED": "0", "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", FOUR], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["unscoped"] == []
    coll = got["collectives"]
    assert coll and all(s is not None for _, _, s in coll), coll
    assert {s for _, opc, s in coll if opc.startswith("all-to-all")} == {"handoff"}, coll
    assert {s for _, opc, s in coll if opc.startswith("collective-permute")} == {"encoder", "decoder"}, coll


# --- the trainer's phase spans ---------------------------------------------


def test_trainer_writes_its_four_phase_spans_with_their_numbers(tmp_path):
    import jax
    from jax.profiler import ProfileData

    c, trainer, feed = _small_trainer("hnmt-wmt-b896-1chip", jax.devices())
    with c.model.program_context(c.config):
        trainer.run(3, log_every=1 << 40)  # every bucket compiled before the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        feed.annotate = True
        trainer.run(2, log_every=2)
        jax.profiler.stop_trace()
    spans = []
    for plane in ProfileData.from_file(str(trace_reduce.find_xplane(tmp_path))).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats)) for ev in line.events
                          if ev.name.startswith("trainer.") or ev.name == "batch_fetch"]
    spans.sort()
    names = [n for _, _, n, _ in spans]
    assert names == ["trainer.fetch", "batch_fetch", "trainer.put", "trainer.dispatch", "trainer.sync"] * 2
    for k in range(2):
        fetch, bf, put, dispatch, sync = spans[5 * k:5 * k + 5]
        assert fetch[0] <= bf[0] and bf[1] <= fetch[1] <= put[0] and put[1] <= dispatch[0] and dispatch[1] <= sync[0]
        pad = feed.feed.history[3 + k][0]  # source and target padded alike
        assert dispatch[3] == {"rows": 8, "src_len": pad, "tgt_len": pad}
        assert put[3] == {"bytes": 8 * pad * (3 * 4 + 2 * 1)}  # three int32 arrays, two masks
    assert len(trainer.history) == 1  # the logging step read its loss inside trainer.sync


def test_layer_trace_runs_a_small_cell_on_the_cpu():
    """``layer_trace.run`` end to end at smoke widths: both windows, the
    accepted per-layer metrics of the traced one, and the phase spans of
    every traced step in the trace (a CPU trace has no device plane, so
    no scope or idle time is read)."""
    import jax
    import layer_trace
    import workload

    cell = workload.load(ROOT, "hnmtif-wmt-b224-1chip")
    cell.config.update(SMOKE)
    cell.traffic["pairs_per_step"] = 8
    out = layer_trace.run(cell, jax.devices(), seed=SEED, seconds=0.3, trace_seconds=0.3)
    assert out["train_tok_s"]["untraced"] > 0 and out["train_tok_s"]["traced"] > 0
    assert out["steps"]["traced_in_trace"] == out["steps"]["traced"] > 0
    assert out["scopes"] == [] and out["layers"] == {}
    assert out["per_layer"]["compile_s"] > 0

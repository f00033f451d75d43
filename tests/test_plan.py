"""ExecutionPlan: schedule arithmetic, the microbatched wavefront's
tick-count contract, microbatch/overlap equivalence, and the extended
analytic Table-3 model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import strategy as st
from repro.core.hybrid import pipeline_activation_model, scaling_factor_model, strategy_comm_cost
from repro.core.plan import ExecutionPlan, ServePlan, WavefrontSchedule
from repro.core.schedule import PipelineSchedule
from repro.launch.mesh import make_mesh
from repro.models import seq2seq as s2s
from repro.train.trainer import make_grad_fn


# ---------------------------------------------------------------------------
# schedule arithmetic
# ---------------------------------------------------------------------------


def test_wavefront_schedule_amortizes_bubble():
    """k microbatches through ONE wavefront: k*S + NS - 1 ticks — the
    (NS-1)-tick fill/drain is paid once per step, not once per microbatch."""
    for S, NS in [(13, 4), (25, 4), (8, 8), (5, 1)]:
        base = WavefrontSchedule(seq_len=S, num_stages=NS)
        assert base.ticks == S + NS - 1
        for k in (2, 4):
            sched = WavefrontSchedule(seq_len=S, num_stages=NS, micro_batches=k)
            assert sched.ticks == k * S + NS - 1
            assert sched.naive_ticks == k * (S + NS - 1)
            if NS > 1:
                assert sched.ticks < sched.naive_ticks
                assert sched.bubble_fraction < base.bubble_fraction
            assert sched.fill_drain_ticks == NS - 1


def test_plan_microbatch_placement():
    """Pipelined plans interleave microbatches inside the wavefront (no
    accumulation scan); non-pipelined plans accumulate."""
    mesh = make_mesh((1, 1), ("data", "model"))
    piped = ExecutionPlan(strategy=st.Strategy.HYBRID, mesh=mesh, micro_batches=4, use_pipeline=True)
    assert piped.pipelined and piped.accum_steps == 1
    assert piped.wavefront(10).micro_batches == 4
    accum = ExecutionPlan(strategy=st.Strategy.HYBRID, mesh=mesh, micro_batches=4)
    assert not accum.pipelined and accum.accum_steps == 4
    assert accum.wavefront(10).micro_batches == 1
    # DATA never pipelines (no model-parallel backbone to wavefront)
    data = ExecutionPlan(strategy=st.Strategy.DATA, mesh=mesh, micro_batches=4, use_pipeline=True)
    assert not data.pipelined and data.accum_steps == 4
    with pytest.raises(ValueError):
        ExecutionPlan(strategy=st.Strategy.HYBRID, micro_batches=0)
    with pytest.raises(ValueError):
        ExecutionPlan(strategy=st.Strategy.HYBRID, mesh=mesh, micro_batches=3).validate_batch(32)


class _ShapeMesh:
    """Shape-only mesh stand-in: batch_shard_size/validate_batch read only
    axis_names and devices.shape, and a real 2x4 mesh needs 8 devices
    (multi-device execution tests live in test_multidevice.py)."""

    axis_names = ("data", "model")
    devices = np.zeros((2, 4))


def test_validate_batch_rejects_unshardable_batch():
    """The plan-vs-backbone seam: ``batch_shard_backbone`` raises at trace
    time on global_batch % data_shards != 0, so ``validate_batch`` must
    reject exactly that case up front instead of letting the plan validate
    and then crash mid-train (the runtime side of this pin — the backbone's
    own raise — lives in test_multidevice.py)."""
    plan = ExecutionPlan(strategy=st.Strategy.HYBRID, mesh=_ShapeMesh())
    assert plan.batch_shard_size() == 2  # (pod, data) axes -> data=2
    with pytest.raises(ValueError, match="batch shards"):
        plan.validate_batch(9)
    plan.validate_batch(8)
    # DATA shards the batch over ALL axes -> 2*4
    data = ExecutionPlan(strategy=st.Strategy.DATA, mesh=_ShapeMesh())
    assert data.batch_shard_size() == 8
    with pytest.raises(ValueError, match="batch shards"):
        data.validate_batch(12)
    data.validate_batch(16)
    # micro slices of an evenly-shardable batch must still divide
    with pytest.raises(ValueError, match="micro_batches"):
        ExecutionPlan(strategy=st.Strategy.HYBRID, mesh=_ShapeMesh(), micro_batches=3).validate_batch(8)


def test_serve_plan_slot_sharding():
    """ServePlan mirrors the training-side seam: a mesh it cannot use is a
    construction error, and the slot-table placement derives from the plan."""
    with pytest.raises(ValueError, match="unsharded"):
        ServePlan(mesh=_ShapeMesh())  # strategy='single' would ignore the mesh
    with pytest.raises(ValueError, match="max_slots"):
        ServePlan(strategy="hybrid", mesh=_ShapeMesh(), max_slots=3)  # 3 % 2

    class ModelOnlyMesh:  # batch_spec yields an EMPTY axis group: P((),)
        axis_names = ("model",)
        devices = np.zeros(8)

    with pytest.raises(ValueError, match="no.*batch axes"):
        ServePlan(strategy="hybrid", mesh=ModelOnlyMesh(), max_slots=8)
    plan = ServePlan(strategy="hybrid", mesh=_ShapeMesh(), max_slots=4)
    assert plan.data_shard_size() == 2 and plan.slot_spec() == st.batch_spec(st.Strategy.HYBRID, _ShapeMesh())
    assert ServePlan(strategy="data", mesh=_ShapeMesh(), max_slots=8).data_shard_size() == 8
    # meshless plans stay unconstrained
    assert ServePlan().data_shard_size() == 1 and ServePlan().slot_sharding(3) is None
    # slot_sharding places the slot dim only: one real (1-device) mesh leaf
    mesh = make_mesh((1,), ("data",))
    sh = ServePlan(strategy="data", mesh=mesh, max_slots=2).slot_sharding(3)
    assert sh.spec == jax.sharding.PartitionSpec(("data",), None, None)


def test_serve_plan_model_axis():
    """The model-axis serving seam: strategy='model' accepts a mesh with NO
    batch axes (slots replicate; weights, kv heads and the vocab head
    shard), ``model_shard_size`` reads the model axis, and ``validate_for``
    rejects meshes whose model axis cannot divide the dimensions it would
    shard — before any engine is built."""

    class ModelOnlyMesh:  # shape-only: plan validation reads names + shape
        axis_names = ("model",)
        devices = np.zeros(8)

    plan = ServePlan(strategy="model", mesh=ModelOnlyMesh(), max_slots=4)
    assert plan.model_shard_size() == 8 and plan.data_shard_size() == 1
    # HYBRID still demands a batch axis: only MODEL may replicate the slots
    with pytest.raises(ValueError, match="no.*batch axes"):
        ServePlan(strategy="hybrid", mesh=ModelOnlyMesh(), max_slots=8)

    tfm_cfg = get_config("qwen3-1.7b", smoke=True)  # kv=4, vocab=512
    s2s_cfg = get_config("seq2seq-rnn", smoke=True)  # d_model=256, vocab=512
    # 8 does not divide the smoke config's 4 kv heads -> the kv cache
    # cannot head-shard
    with pytest.raises(ValueError, match="num_kv_heads"):
        ServePlan(strategy="model", mesh=ModelOnlyMesh(), max_slots=4,
                  cache_policy="window", window=8, prefill_chunk=8).validate_for(tfm_cfg)

    class ThreeMesh:
        axis_names = ("model",)
        devices = np.zeros(3)

    with pytest.raises(ValueError, match="vocab_size"):
        ServePlan(strategy="model", mesh=ThreeMesh(), max_slots=4).validate_for(tfm_cfg)

    class HugeMesh:  # divides the vocab (512) but not d_model (256)
        axis_names = ("model",)
        devices = np.zeros(512)

    with pytest.raises(ValueError, match="d_model"):
        ServePlan(strategy="model", mesh=HugeMesh(), max_slots=4,
                  cache_policy="encdec_memory").validate_for(s2s_cfg)

    # fit_model_axis picks the largest axis validate_for accepts
    assert st.fit_model_axis(tfm_cfg, "full_kv", 8) == 4
    assert st.fit_model_axis(s2s_cfg, "encdec_memory", 8) == 8
    assert st.fit_model_axis(get_config("xlstm-350m", smoke=True), "recurrent", 8) == 8

    class FittedMesh:
        axis_names = ("model",)
        devices = np.zeros(4)

    fitted = ServePlan(strategy="model", mesh=FittedMesh(), max_slots=4,
                       cache_policy="window", window=8, prefill_chunk=8)
    fitted.validate_for(tfm_cfg)  # 4 | kv=4 and 4 | vocab=512: accepted
    assert fitted.model_shard_size() == 4


def test_serve_bench_trajectory_roofline_agreement():
    """The committed mesh-sweep trajectory (experiments/bench/
    serve_bench.json) must show the decode-tick roofline predicting the
    measured winner at EVERY swept point, and the roofline must predict the
    slot-vs-model crossover: on a host with cores >= devices the model-axis
    layout beats single-device at bench scale (weights shard instead of
    replicate), while a one-core host serializes every layout and
    single-device wins on overhead."""
    import dataclasses
    import json
    import os

    from repro.configs.base import reduced
    from repro.launch.roofline import predict_serve_winner

    path = os.path.join(os.path.dirname(__file__), "..", "experiments", "bench", "serve_bench.json")
    with open(path) as f:
        traj = json.load(f)
    winners = [r for entry in traj for r in entry["records"] if r.get("kind") == "winner"]
    assert winners, "trajectory has no winner records — rerun benchmarks/serve_bench.py --mesh"
    for w in winners:
        assert w["match"], f"roofline missed the measured winner at {w}"
    # the crossover, as the roofline states it for a host that can actually
    # run 8 concurrent device programs
    bench_cfg = dataclasses.replace(
        reduced(get_config("qwen3-1.7b")), d_model=1024, num_heads=16, num_kv_heads=8,
        head_dim=64, d_ff=4096, vocab_size=16384, emb_size=1024,
    )
    for slots in (8, 32):
        assert predict_serve_winner(bench_cfg, devices=8, slots=slots, cores=8,
                                    cache_policy="window", window=64) == "model"
        assert predict_serve_winner(bench_cfg, devices=8, slots=slots, cores=1,
                                    cache_policy="window", window=64) == "single"


def test_plan_stage_kernel_validation():
    """stage_kernel is a closed vocabulary; the default is the jnp math."""
    assert ExecutionPlan(strategy=st.Strategy.HYBRID).stage_kernel == "jnp"
    for sk in ("jnp", "pallas"):
        assert ExecutionPlan(strategy=st.Strategy.HYBRID, stage_kernel=sk).stage_kernel == sk
    # the backend decides whether a kernel is interpreted; no option does
    for bad in ("cuda", "pallas_interpret"):
        with pytest.raises(ValueError):
            ExecutionPlan(strategy=st.Strategy.HYBRID, stage_kernel=bad)
    from repro.core import pipeline as pl

    with pytest.raises(ValueError):
        pl.pipeline_lstm(
            make_mesh((1, 1), ("data", "model")), {}, jnp.zeros((1, 1, 1)),
            in_dim=1, stage_kernel="nope",
        )


def test_plan_split_head_partition():
    tree = {"head": 1, "encoder": 2, "decoder": 3, "src_emb": 4}
    head, body = ExecutionPlan.split_head(tree)
    assert set(head) == {"head"} and set(body) == {"encoder", "decoder", "src_emb"}
    assert ExecutionPlan.merge_head(head, body) == tree


# ---------------------------------------------------------------------------
# tick-count contract: the lowered wavefront scan runs exactly sched.ticks
# ---------------------------------------------------------------------------


def _scan_lengths(obj, out):
    """Collect every lax.scan trip count in a (Closed)Jaxpr, recursively."""
    jaxpr = getattr(obj, "jaxpr", obj)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                if hasattr(u, "eqns") or hasattr(u, "jaxpr"):
                    _scan_lengths(u, out)
    return out


@pytest.mark.parametrize("k", [1, 2, 4])
def test_pipeline_tick_count(k):
    """pipeline_lstm with micro_batches=k issues ONE wavefront of
    k*S + NS - 1 ticks per step (the bubble amortized over k), asserted on
    the traced scan's trip count."""
    from repro.core import pipeline as pl
    from repro.models import lstm
    from repro.models.common import Initializer

    mesh = make_mesh((1, 1), ("data", "model"))
    L, e, h, B, S = 2, 8, 16, 8, 6
    params, _ = lstm.init_stacked_lstm(Initializer(jax.random.key(0)), "enc", L, e, h)
    stacked, _ = pl.stack_pipeline_params(params, 1)
    x = jnp.zeros((B, S, e), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda st_, xx: pl.pipeline_lstm(mesh, st_, xx, in_dim=e, micro_batches=k)
    )(stacked, x)
    lengths = _scan_lengths(jaxpr, [])
    sched = WavefrontSchedule(seq_len=S, num_stages=1, micro_batches=k)
    assert sched.ticks in lengths, (lengths, sched.ticks)
    # the naive per-microbatch schedule would need k scans of S+NS-1 ticks;
    # exactly one wavefront scan may appear
    assert lengths.count(sched.ticks) == 1


# ---------------------------------------------------------------------------
# microbatch equivalence: plan(micro_batches=k) == single-batch reference
# ---------------------------------------------------------------------------


def _fixed_batch(cfg, B=8, M=12, N=10):
    ks = jax.random.split(jax.random.key(1), 3)
    return {
        "src": jax.random.randint(ks[0], (B, M), 3, cfg.vocab_size),
        "tgt_in": jax.random.randint(ks[1], (B, N), 3, cfg.vocab_size),
        "tgt_out": jax.random.randint(ks[2], (B, N), 3, cfg.vocab_size),
        "src_mask": jnp.ones((B, M), bool),
        "tgt_mask": jnp.ones((B, N), bool),
    }


def _assert_grads_match(ref, got):
    """Every grad leaf of ``got`` allclose to ``ref`` at fp32."""
    flat_r, tree_r = jax.tree.flatten(ref)
    flat_g, tree_g = jax.tree.flatten(got)
    assert tree_r == tree_g
    for a, b in zip(flat_r, flat_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


def _autodiff_reference(cfg, strat, mesh, params, batch, rng):
    """Loss and grads of the unpipelined plan: autodiff of the plain
    ``run_stacked_lstm`` scans, what the wavefront's backward must equal."""
    loss, _, grads = jax.jit(make_grad_fn(cfg, ExecutionPlan(strategy=strat, mesh=mesh)))(params, batch, rng)
    return loss, grads


@pytest.mark.parametrize("strat", [st.Strategy.HYBRID, st.Strategy.MODEL])
def test_plan_microbatch_matches_reference(strat):
    """Loss/grads from ExecutionPlan(micro_batches=k) — both the wavefront
    interleave and the accumulation scan — match the single-batch reference
    within tolerance on a 1-device mesh."""
    # fp32: equivalence across differently-lowered schedules needs more
    # mantissa than bf16's 8 bits (one bf16 ulp at loss~6 is ~0.03)
    cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), dropout=0.0, dtype="float32")
    params, _ = s2s.init_seq2seq(jax.random.key(0), cfg)
    batch = _fixed_batch(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.key(9)

    ref_plan = ExecutionPlan(strategy=strat, mesh=mesh)
    loss_ref, _, g_ref = jax.jit(make_grad_fn(cfg, ref_plan))(params, batch, rng)

    for plan in (
        ExecutionPlan(strategy=strat, mesh=mesh, micro_batches=2, use_pipeline=True),
        ExecutionPlan(strategy=strat, mesh=mesh, micro_batches=2),
    ):
        loss, _, g = jax.jit(make_grad_fn(cfg, plan))(params, batch, rng)
        assert abs(float(loss) - float(loss_ref)) < 1e-4
        gerr = max(
            float(jnp.abs(a - b).max()) for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g))
        )
        assert gerr < 1e-3, (plan.pipelined, gerr)


def test_overlap_grad_sync_is_pure_reordering():
    """The delayed head-grad psum changes WHEN the all-reduce runs, never
    the result: overlap=True grads equal overlap=False grads."""
    # fp32: equivalence across differently-lowered schedules needs more
    # mantissa than bf16's 8 bits (one bf16 ulp at loss~6 is ~0.03)
    cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), dropout=0.0, dtype="float32")
    params, _ = s2s.init_seq2seq(jax.random.key(0), cfg)
    batch = _fixed_batch(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.key(7)
    base = ExecutionPlan(strategy=st.Strategy.HYBRID, mesh=mesh, micro_batches=4)
    over = dataclasses.replace(base, overlap=True)
    l1, e1, g1 = jax.jit(make_grad_fn(cfg, base))(params, batch, rng)
    l2, e2, g2 = jax.jit(make_grad_fn(cfg, over))(params, batch, rng)
    assert abs(float(l1) - float(l2)) < 1e-6
    assert float(e1["denom"]) == float(e2["denom"])
    gerr = max(float(jnp.abs(a - b).max()) for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
    assert gerr < 1e-6, gerr


# ---------------------------------------------------------------------------
# stage_kernel equivalence: the fused Pallas cell inside the wavefront is a
# pure compute swap — same loss, same grads as the jnp cell math
# ---------------------------------------------------------------------------


@pytest.mark.pallas
@pytest.mark.parametrize("strat,schedule,k", [
    pytest.param(st.Strategy.HYBRID, "gpipe", 2, id="hybrid"),
    pytest.param(st.Strategy.MODEL, "gpipe", 2, id="model"),
    pytest.param(st.Strategy.HYBRID, "gpipe", 1, id="hybrid-gpipe-k1"),
    pytest.param(st.Strategy.HYBRID, "1f1b", 2, id="hybrid-1f1b-k2"),
    pytest.param(st.Strategy.HYBRID, "zerobubble", 1, id="hybrid-zerobubble-k1"),
    pytest.param(st.Strategy.HYBRID, "interleaved", 2, id="hybrid-interleaved-k2"),
])
def test_pipelined_train_step_stage_kernel_parity(strat, schedule, k):
    """A pipelined train step with stage_kernel="pallas" (the
    fused LSTM cell kernel, interpreted on CPU) matches the "jnp" path,
    and both match autodiff of the plain ``run_stacked_lstm``: loss and
    every grad leaf allclose at fp32.  The jnp cell's backward runs from
    the gates its recompute pass stashed, the Pallas cell's rebuilds them,
    so this is the guarantee that the two backward paths and the kernel
    in the hot path can never silently diverge."""
    cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), dropout=0.0, dtype="float32")
    params, _ = s2s.init_seq2seq(jax.random.key(0), cfg)
    batch = _fixed_batch(cfg, B=4, M=8, N=6)
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.key(3)
    vs = 2 if schedule == "interleaved" else 1

    loss_ref, g_ref = _autodiff_reference(cfg, strat, mesh, params, batch, rng)
    losses, grads = {}, {}
    for sk in ("jnp", "pallas"):
        plan = ExecutionPlan(
            strategy=strat, mesh=mesh, micro_batches=k, use_pipeline=True, stage_kernel=sk,
            schedule=schedule, virtual_stages=vs,
        )
        assert plan.pipelined
        losses[sk], _, grads[sk] = jax.jit(make_grad_fn(cfg, plan))(params, batch, rng)
    assert abs(float(losses["jnp"]) - float(losses["pallas"])) < 1e-5
    assert abs(float(losses["jnp"]) - float(loss_ref)) < 1e-5
    _assert_grads_match(grads["jnp"], grads["pallas"])
    _assert_grads_match(g_ref, grads["jnp"])


# ---------------------------------------------------------------------------
# PipelineSchedule: the schedule-driven backward (gpipe vs 1f1b)
# ---------------------------------------------------------------------------


@pytest.mark.pipeline
def test_plan_schedule_field_validation():
    """schedule is a closed vocabulary threaded from the plan into the
    PipelineSchedule the executor consumes."""
    assert ExecutionPlan(strategy=st.Strategy.HYBRID).schedule == "gpipe"
    mesh = make_mesh((1, 1), ("data", "model"))
    for kind in ("gpipe", "1f1b"):
        plan = ExecutionPlan(
            strategy=st.Strategy.HYBRID, mesh=mesh, micro_batches=3,
            use_pipeline=True, schedule=kind,
        )
        sched = plan.pipeline_schedule(7)
        assert sched.kind == kind and sched.micro_batches == 3
        # the wavefront view is the schedule's own forward arithmetic
        assert plan.wavefront(7).ticks == sched.forward_ticks
    with pytest.raises(ValueError):
        ExecutionPlan(strategy=st.Strategy.HYBRID, schedule="zigzag")
    with pytest.raises(ValueError):
        PipelineSchedule(seq_len=4, num_stages=2, kind="zigzag")
    from repro.core import pipeline as pl

    with pytest.raises(ValueError):
        pl.pipeline_lstm(
            make_mesh((1, 1), ("data", "model")), {}, jnp.zeros((1, 1, 1)),
            in_dim=1, schedule="nope",
        )


@pytest.mark.pipeline
def test_schedule_1f1b_stash_bound_and_gpipe_identity():
    """The acceptance contract, read off the table: 1f1b peak stashed
    microbatches per stage <= min(k, NS) (gpipe holds all k), and the gpipe
    forward table IS WavefrontSchedule's tick arithmetic."""
    for S, NS, k in [(6, 1, 4), (5, 2, 3), (3, 4, 8), (4, 4, 2)]:
        gp = PipelineSchedule(seq_len=S, num_stages=NS, micro_batches=k, kind="gpipe")
        ob = PipelineSchedule(seq_len=S, num_stages=NS, micro_batches=k, kind="1f1b")
        for s in range(NS):
            assert gp.peak_live_microbatches(s) == k
            assert ob.peak_live_microbatches(s) <= min(k, NS)
            assert ob.peak_stash_steps(s) <= min(k, NS) * S
        wf = WavefrontSchedule(seq_len=S, num_stages=NS, micro_batches=k)
        fwd = {(u.stage, u.micro, u.t): u.tick for u in gp.table() if u.kind == "F"}
        for (s, m, t), tick in fwd.items():
            assert tick == s + m * S + t  # WavefrontSchedule arithmetic
        assert max(fwd.values()) + 1 == wf.ticks == gp.forward_ticks
        # both kinds retire every unit; gpipe's timeline is the two mirrored
        # wavefronts exactly
        assert gp.total_ticks == 2 * wf.ticks
        assert len(ob.table()) == len(gp.table()) == gp.work_units


@pytest.mark.pipeline
@pytest.mark.parametrize("strat,k", [
    pytest.param(st.Strategy.HYBRID, 4, id="hybrid"),
    pytest.param(st.Strategy.MODEL, 4, id="model"),
    pytest.param(st.Strategy.HYBRID, 1, id="hybrid-k1"),
    pytest.param(st.Strategy.HYBRID, 2, id="hybrid-k2"),
])
def test_pipelined_train_step_schedule_parity(strat, k):
    """Train-step gradient parity gpipe vs 1f1b (fp32): the 1F1B backward
    is a pure reordering of the same per-microbatch gradient sums, so loss
    and every grad leaf must agree — while the schedule table certifies the
    1f1b stash stays within min(k, NS) microbatches per stage.  Both equal
    autodiff of the plain ``run_stacked_lstm``: the backward from the
    recompute pass's gate stash is the scan's exact gradient."""
    cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), dropout=0.0, dtype="float32")
    params, _ = s2s.init_seq2seq(jax.random.key(0), cfg)
    batch = _fixed_batch(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.key(11)
    loss_ref, g_ref = _autodiff_reference(cfg, strat, mesh, params, batch, rng)
    losses, grads = {}, {}
    for kind in ("gpipe", "1f1b"):
        plan = ExecutionPlan(
            strategy=strat, mesh=mesh, micro_batches=k, use_pipeline=True, schedule=kind,
        )
        assert plan.pipelined
        sched = plan.pipeline_schedule(batch["tgt_in"].shape[1])
        peak = max(sched.peak_live_microbatches(s) for s in range(sched.num_stages))
        if kind == "1f1b":
            assert peak <= min(k, sched.num_stages)
        else:
            assert peak == k
        losses[kind], _, grads[kind] = jax.jit(make_grad_fn(cfg, plan))(params, batch, rng)
        assert abs(float(losses[kind]) - float(loss_ref)) < 1e-5, kind
        _assert_grads_match(g_ref, grads[kind])
    assert abs(float(losses["gpipe"]) - float(losses["1f1b"])) < 1e-5
    _assert_grads_match(grads["gpipe"], grads["1f1b"])


@pytest.mark.pipeline
@pytest.mark.pallas
@pytest.mark.parametrize("strat", [st.Strategy.HYBRID, st.Strategy.MODEL])
def test_pipelined_train_step_schedule_parity_pallas(strat):
    """The same gpipe-vs-1f1b parity with the fused Pallas cell kernel
    (interpret mode) computing the wavefront stages: the schedule swap and
    the kernel dispatch compose without numeric drift."""
    cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), dropout=0.0, dtype="float32")
    params, _ = s2s.init_seq2seq(jax.random.key(0), cfg)
    batch = _fixed_batch(cfg, B=4, M=8, N=6)
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.key(13)
    losses, grads = {}, {}
    for kind in ("gpipe", "1f1b"):
        plan = ExecutionPlan(
            strategy=strat, mesh=mesh, micro_batches=2, use_pipeline=True,
            schedule=kind, stage_kernel="pallas",
        )
        losses[kind], _, grads[kind] = jax.jit(make_grad_fn(cfg, plan))(params, batch, rng)
    assert abs(float(losses["gpipe"]) - float(losses["1f1b"])) < 1e-5
    for a, b in zip(jax.tree.leaves(grads["gpipe"]), jax.tree.leaves(grads["1f1b"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


@pytest.mark.pipeline
def test_pipeline_activation_model_1f1b_bounds_memory():
    """The analytic memory term, at FIXED per-microbatch batch (raising k
    raises the global batch — the Ott et al. large-batch lever): gpipe's
    predicted stash grows linearly with micro_batches, 1f1b's saturates at
    the min(k, NS) depth bound."""
    cfg = get_config("seq2seq-rnn")
    B_mb, NS = 64, 4
    kw = dict(num_stages=NS, src_len=25, tgt_len=25)
    gp, ob = {}, {}
    for k in (1, 2, 4, 8, 16):
        gp[k] = pipeline_activation_model(cfg, schedule="gpipe", micro_batches=k, batch=B_mb * k, **kw)["peak_stash_bytes"]
        ob[k] = pipeline_activation_model(cfg, schedule="1f1b", micro_batches=k, batch=B_mb * k, **kw)["peak_stash_bytes"]
    assert gp[1] == ob[1]  # k=1: the schedules coincide
    assert abs(gp[16] - 16 * gp[1]) < 1e-6 * gp[16]  # gpipe: linear in k
    for k in (2, 4, 8, 16):
        assert ob[k] <= gp[k]
        assert ob[k] <= min(k, NS) * ob[1] + 1e-9  # the table's depth bound
    assert ob[16] == ob[8]  # saturated: flat in k past the pipeline depth
    # one unit: each layer's h and c and the jnp cell's four gate
    # pre-activations, fp32; the fused Pallas cell's backward rebuilds them
    unit = lambda sk: pipeline_activation_model(
        cfg, schedule="gpipe", micro_batches=1, batch=B_mb, stage_kernel=sk, **kw)["unit_bytes"]
    lp = cfg.num_layers // NS
    assert unit("jnp") == (4 + 2) * lp * B_mb * cfg.d_model * 4
    assert unit("pallas") == 2 * lp * B_mb * cfg.d_model * 4


@pytest.mark.pipeline
@pytest.mark.parametrize("stage_kernel", ["jnp", "pallas"])
def test_pipeline_backward_gemm_count(stage_kernel):
    """GEMMs per iteration of each loop of the compiled step
    (``hlo_analysis.loop_gemm_counts``), 4 layers on a (1, 1) mesh (Lp =
    4): per side, the forward scan and the recompute pass (phase A) run the
    2·Lp gate GEMMs, and the backward loop (phase B) runs from the jnp
    cell's stashed gates, so only the gradient's 4·Lp (dx, dh, dWx, dWh).
    The fused Pallas cell keeps its gates, so its phase B rebuilds them:
    6·Lp (the kernel's own GEMMs sit in its interpreted grid loops, nested
    and not counted)."""
    from repro.launch.hlo_analysis import loop_gemm_counts

    Lp = 4
    cfg = dataclasses.replace(
        get_config("seq2seq-rnn", smoke=True), num_layers=Lp, dropout=0.0, dtype="float32"
    )
    params, _ = s2s.init_seq2seq(jax.random.key(0), cfg)
    batch = _fixed_batch(cfg, B=4, M=6, N=5)
    plan = ExecutionPlan(
        strategy=st.Strategy.HYBRID, mesh=make_mesh((1, 1), ("data", "model")),
        use_pipeline=True, stage_kernel=stage_kernel,
    )
    compiled = jax.jit(make_grad_fn(cfg, plan)).lower(params, batch, jax.random.key(0)).compile()
    counts = sorted(loop_gemm_counts(compiled.as_text()))
    if stage_kernel == "jnp":
        assert counts == [2 * Lp] * 4 + [4 * Lp] * 2, counts
    else:
        assert counts[-2:] == [6 * Lp] * 2, counts
        assert 4 * Lp not in counts, counts


@pytest.mark.pipeline
def test_zerobubble_bubble_strictly_below_1f1b():
    """The acceptance contract for the split backward: at the same (k, NS)
    the zerobubble table's bubble fraction is strictly below 1f1b's
    whenever 1f1b has any bubble to fill, because the W units land in the
    cooldown idle slots instead of extending the fused B critical path."""
    for S, NS, k in [(5, 2, 3), (3, 4, 8), (4, 4, 2), (6, 2, 4)]:
        ob = PipelineSchedule(seq_len=S, num_stages=NS, micro_batches=k, kind="1f1b")
        zb = PipelineSchedule(seq_len=S, num_stages=NS, micro_batches=k, kind="zerobubble")
        assert zb.work_units == 3 * NS * k * S  # F, B, W each once per step
        if ob.bubble_fraction > 0:
            assert zb.bubble_fraction < ob.bubble_fraction, (S, NS, k)
        # the split backward also shortens the lockstep critical path
        assert zb.time_stretch() <= ob.time_stretch() + 1e-12


@pytest.mark.pipeline
def test_interleaved_v1_is_gpipe():
    """interleaved with one chunk per device is literally the gpipe table."""
    for S, NS, k in [(4, 2, 3), (3, 4, 2)]:
        gp = PipelineSchedule(seq_len=S, num_stages=NS, micro_batches=k, kind="gpipe")
        il = PipelineSchedule(seq_len=S, num_stages=NS, micro_batches=k, kind="interleaved", chunks=1)
        assert il.table() == gp.table()
        assert il.bubble_fraction == gp.bubble_fraction
    with pytest.raises(ValueError):
        PipelineSchedule(seq_len=4, num_stages=2, kind="gpipe", chunks=2)


@pytest.mark.pipeline
@pytest.mark.parametrize("strat,k", [
    pytest.param(st.Strategy.HYBRID, 4, id="hybrid"),
    pytest.param(st.Strategy.MODEL, 4, id="model"),
    pytest.param(st.Strategy.HYBRID, 1, id="hybrid-k1"),
    pytest.param(st.Strategy.HYBRID, 2, id="hybrid-k2"),
])
def test_pipelined_train_step_new_schedule_parity(strat, k):
    """zerobubble and interleaved (v=2) execute a pure reordering of the
    same per-microbatch gradient sums: loss and every grad leaf must match
    the gpipe execution, and autodiff of the plain ``run_stacked_lstm``,
    within fp32 reordering noise."""
    cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), dropout=0.0, dtype="float32")
    params, _ = s2s.init_seq2seq(jax.random.key(0), cfg)
    batch = _fixed_batch(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = jax.random.key(17)
    plans = {
        "gpipe": dict(schedule="gpipe"),
        "zerobubble": dict(schedule="zerobubble"),
        "interleaved_v2": dict(schedule="interleaved", virtual_stages=2),
    }
    loss_ref, g_ref = _autodiff_reference(cfg, strat, mesh, params, batch, rng)
    losses, grads = {}, {}
    for name, kw in plans.items():
        plan = ExecutionPlan(
            strategy=strat, mesh=mesh, micro_batches=k, use_pipeline=True, **kw,
        )
        losses[name], _, grads[name] = jax.jit(make_grad_fn(cfg, plan))(params, batch, rng)
    for name in ("zerobubble", "interleaved_v2"):
        assert abs(float(losses["gpipe"]) - float(losses[name])) < 1e-5, name
        for a, b in zip(jax.tree.leaves(grads["gpipe"]), jax.tree.leaves(grads[name])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)
    for name in plans:
        assert abs(float(losses[name]) - float(loss_ref)) < 1e-5, name
        _assert_grads_match(g_ref, grads[name])


@pytest.mark.pipeline
def test_plan_virtual_stages_validation():
    """virtual_stages is the interleaved lever only: v >= 1 always, v > 1
    demands the interleaved schedule (other kinds have no chunk column)."""
    with pytest.raises(ValueError):
        ExecutionPlan(strategy=st.Strategy.HYBRID, virtual_stages=0)
    with pytest.raises(ValueError):
        ExecutionPlan(
            strategy=st.Strategy.HYBRID, micro_batches=2, use_pipeline=True,
            schedule="gpipe", virtual_stages=2,
        )
    plan = ExecutionPlan(
        strategy=st.Strategy.HYBRID, micro_batches=2, use_pipeline=True,
        schedule="interleaved", virtual_stages=2,
    )
    assert plan.pipeline_schedule(5).chunks == 2


# ---------------------------------------------------------------------------
# ServePlan: the serving half of the execution vocabulary
# ---------------------------------------------------------------------------


def test_serve_plan_validation_errors():
    """The closed vocabularies and the structural constraints: bad policy /
    admission / stage_kernel, non-divisible prefill chunk, windowless (or
    chunk-wrapping) window policy, static batch overflow (slots < batch)."""
    with pytest.raises(ValueError):
        ServePlan(cache_policy="paged")
    with pytest.raises(ValueError):
        ServePlan(admission="preemptive")
    with pytest.raises(ValueError):
        ServePlan(stage_kernel="cuda")
    with pytest.raises(ValueError):
        ServePlan(max_slots=0)
    with pytest.raises(ValueError):
        ServePlan(max_len=48, prefill_chunk=32)  # chunk must tile capacity
    with pytest.raises(ValueError):
        ServePlan(cache_policy="window")  # window policy needs a window
    with pytest.raises(ValueError):
        ServePlan(cache_policy="window", window=8, prefill_chunk=16, max_len=32)  # chunk wraps buffer
    with pytest.raises(ValueError):
        ServePlan(cache_policy="full_kv", window=8)  # stray window
    # slots < batch only matters for static admission (continuous queues)
    plan = ServePlan(max_slots=2, admission="static")
    with pytest.raises(ValueError):
        plan.validate_batch(3)
    plan.validate_batch(2)
    ServePlan(max_slots=2, admission="continuous").validate_batch(64)


def test_plan_validation_errors_name_field_and_value():
    """Every __post_init__ raise names the offending field AND the value it
    got — pinned here so error text stays actionable (the audit CLI surfaces
    these verbatim when a matrix entry is mis-specified)."""
    import re

    # ExecutionPlan: the overlap/bucket levers
    with pytest.raises(ValueError, match=re.escape("bucket_bytes=4096 requires overlap=True, got overlap=False")):
        ExecutionPlan(strategy=st.Strategy.DATA, bucket_bytes=4096)
    with pytest.raises(ValueError, match=r"overlap=True with use_pipeline=True"):
        ExecutionPlan(
            strategy=st.Strategy.HYBRID, mesh=make_mesh((1, 1), ("data", "model")),
            micro_batches=2, use_pipeline=True, overlap=True,
        )
    with pytest.raises(ValueError, match=r"virtual_stages=2 requires schedule='interleaved'.*got 'gpipe'"):
        ExecutionPlan(
            strategy=st.Strategy.HYBRID, micro_batches=2, use_pipeline=True,
            schedule="gpipe", virtual_stages=2,
        )
    plan = ExecutionPlan(strategy=st.Strategy.DATA)
    with pytest.raises(ValueError, match=r"grad_buckets requires bucket_bytes.*got bucket_bytes=None"):
        plan.grad_buckets({"w": jnp.zeros((4, 4))})
    with pytest.raises(ValueError, match=r"seq_len=0, num_stages=2, micro_batches=1"):
        PipelineSchedule(seq_len=0, num_stages=2)

    # ServePlan: cache-policy / paging / speculation levers
    with pytest.raises(ValueError, match=r"cache_policy='window' requires a positive window, got window=None"):
        ServePlan(cache_policy="window")
    with pytest.raises(ValueError, match=r"num_pages=8 without page_size"):
        ServePlan(num_pages=8)
    with pytest.raises(ValueError, match=r"share_prefixes=True requires a paged plan, got page_size=None"):
        ServePlan(share_prefixes=True)
    with pytest.raises(ValueError, match=r"share_prefixes=True requires cache_policy='full_kv'.*cache_policy='window'"):
        ServePlan(cache_policy="window", window=8, prefill_chunk=8,
                  page_size=8, num_pages=64, share_prefixes=True)
    with pytest.raises(ValueError, match=r"draft_len=3 without draft_arch"):
        ServePlan(draft_len=3)
    with pytest.raises(ValueError, match=r"draft_arch='xlstm-350m' does not serve cache_policy='encdec_memory'"):
        ServePlan(cache_policy="encdec_memory", draft_arch="xlstm-350m", draft_len=2)
    with pytest.raises(ValueError, match=r"admission='static' has no draft path"):
        ServePlan(draft_arch="xlstm-350m", draft_len=2, admission="static")


def test_serve_plan_family_policy_matrix():
    """window/full_kv on the recurrent family, recurrent on an attention
    family, and seq2seq <-> encdec_memory mismatches are all rejected."""
    ssm_cfg = get_config("xlstm-350m", smoke=True)
    tfm_cfg = get_config("qwen3-1.7b", smoke=True)
    s2s_cfg = get_config("seq2seq-rnn", smoke=True)
    with pytest.raises(ValueError):
        ServePlan(cache_policy="window", window=8, prefill_chunk=8).validate_for(ssm_cfg)
    with pytest.raises(ValueError):
        ServePlan(cache_policy="full_kv").validate_for(ssm_cfg)
    with pytest.raises(ValueError):
        ServePlan(cache_policy="recurrent").validate_for(tfm_cfg)
    with pytest.raises(ValueError):
        ServePlan(cache_policy="encdec_memory").validate_for(tfm_cfg)
    with pytest.raises(ValueError):
        ServePlan(cache_policy="full_kv").validate_for(s2s_cfg)
    ServePlan(cache_policy="recurrent").validate_for(ssm_cfg)
    ServePlan(cache_policy="encdec_memory").validate_for(s2s_cfg)


def test_serve_plan_for_config_defaults():
    """for_config picks the family's natural policy."""
    assert ServePlan.for_config(get_config("seq2seq-rnn", smoke=True)).cache_policy == "encdec_memory"
    assert ServePlan.for_config(get_config("xlstm-350m", smoke=True)).cache_policy == "recurrent"
    # a sliding-window arch defaults to the rolling buffer, window from cfg
    win = ServePlan.for_config(get_config("qwen3-1.7b", smoke=True), prefill_chunk=16)
    assert win.cache_policy == "window" and win.window == 64
    # hybrid (attn + mamba) archs keep KV entries -> full_kv, not recurrent
    assert ServePlan.for_config(get_config("jamba-v0.1-52b", smoke=True)).cache_policy == "full_kv"


def test_serve_plan_kwargs_round_trip():
    """plan -> engine_kwargs -> plan is the identity (the engine consumes
    exactly the plan, nothing more)."""
    plan = ServePlan(
        cache_policy="window", window=16, max_slots=4, max_len=64,
        prefill_chunk=8, admission="static", stage_kernel="pallas",
    )
    assert ServePlan(**plan.engine_kwargs()) == plan
    assert plan.cache_capacity == 16  # window bounds the rolling buffer
    assert ServePlan(max_len=64).cache_capacity == 64


# ---------------------------------------------------------------------------
# stage_kernel head dispatch: the fused Luong head inside a train step is a
# pure compute swap — same loss, same grads as the jnp head math
# ---------------------------------------------------------------------------


@pytest.mark.pallas
def test_train_step_fused_head_parity():
    """make_grad_fn with stage_kernel="pallas" (fused Luong
    attention head + fused LSTM cells) matches the jnp path: loss and every
    grad leaf allclose at fp32 — the head's custom-vjp recompute backward
    can never silently diverge from the training math."""
    cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), dropout=0.0, dtype="float32")
    params, _ = s2s.init_seq2seq(jax.random.key(0), cfg)
    batch = _fixed_batch(cfg, B=4, M=8, N=6)
    rng = jax.random.key(5)
    losses, grads = {}, {}
    for sk in ("jnp", "pallas"):
        plan = ExecutionPlan(strategy=st.Strategy.SINGLE, stage_kernel=sk)
        losses[sk], _, grads[sk] = jax.jit(make_grad_fn(cfg, plan))(params, batch, rng)
    assert abs(float(losses["jnp"]) - float(losses["pallas"])) < 1e-4
    flat_j, tree_j = jax.tree.flatten(grads["jnp"])
    flat_p, tree_p = jax.tree.flatten(grads["pallas"])
    assert tree_j == tree_p
    for a, b in zip(flat_j, flat_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# analytic model: microbatch-aware bubble and overlap terms
# ---------------------------------------------------------------------------


def test_scaling_model_microbatched_ordering_and_overlap():
    """For every k the Table-3 ordering (data < model < hybrid backbone
    ranking) survives, and hybrid-with-overlap >= hybrid for k > 1 (the
    delayed psum hides k-1 of the k head syncs)."""
    cfg = get_config("seq2seq-rnn")
    kw = dict(devices=4, batch=224, src_len=25, tgt_len=25, flops_per_sec=4.7e12, link_bytes_per_sec=130e9)
    for k in (1, 2, 4):
        data = scaling_factor_model(cfg, strategy="data", micro_batches=k, **dict(kw, batch=256))
        model_if = scaling_factor_model(cfg, strategy="model", input_feeding=True, micro_batches=k, **kw)
        hybrid = scaling_factor_model(cfg, strategy="hybrid", micro_batches=k, **kw)
        hybrid_ov = scaling_factor_model(cfg, strategy="hybrid", micro_batches=k, overlap=True, **kw)
        assert data < model_if < hybrid, (k, data, model_if, hybrid)
        assert hybrid_ov >= hybrid, (k, hybrid_ov, hybrid)
        if k > 1:
            assert hybrid_ov > hybrid, (k, hybrid_ov, hybrid)
    # k=1 must reproduce the un-microbatched model exactly
    assert scaling_factor_model(cfg, strategy="hybrid", micro_batches=1, **kw) == scaling_factor_model(
        cfg, strategy="hybrid", **kw
    )


def test_comm_cost_overlap_hidden_bytes():
    cfg = get_config("seq2seq-rnn")
    kw = dict(devices=4, batch=224, src_len=25, tgt_len=25)
    plain = strategy_comm_cost(cfg, strategy="hybrid", micro_batches=4, **kw)
    over = strategy_comm_cost(cfg, strategy="hybrid", micro_batches=4, overlap=True, **kw)
    assert plain.overlap_hidden == 0.0 and plain.exposed == plain.total
    assert over.total == plain.total  # same bytes cross the wire
    assert over.exposed < over.total  # ... but 3 of the 4 syncs hide under compute
    assert np.isclose(over.overlap_hidden, over.grad_sync * 3 / 4)
    # k=1 keeps the seed semantics
    k1 = strategy_comm_cost(cfg, strategy="hybrid", **kw)
    assert np.isclose(k1.grad_sync * 4, plain.grad_sync)

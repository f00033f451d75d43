"""Multi-device tests: run in a subprocess with a forced 8-device host so
the main pytest process keeps its single-device view (per the brief)."""
import json
import math
import os
import subprocess
import sys
import textwrap

import pytest


ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SRC = os.path.join(ROOT, "src")


def _run(code: str, devices: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=900
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


PREAMBLE = textwrap.dedent(
    """
    import json, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core import strategy as st
    from repro.launch.mesh import make_mesh
    """
)


def test_all_strategies_same_loss_seq2seq():
    code = PREAMBLE + textwrap.dedent(
        """
        from repro.models import seq2seq as S
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("seq2seq-rnn", smoke=True)
        params, specs = S.init_seq2seq(jax.random.key(0), cfg)
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        B, M, N = 8, 12, 10
        batch = S.Seq2SeqBatch(
            src=jax.random.randint(jax.random.key(1), (B, M), 0, cfg.vocab_size),
            tgt_in=jax.random.randint(jax.random.key(2), (B, N), 0, cfg.vocab_size),
            tgt_out=jax.random.randint(jax.random.key(3), (B, N), 0, cfg.vocab_size),
            src_mask=jnp.ones((B, M), bool), tgt_mask=jnp.ones((B, N), bool))
        losses = {}
        for strat in st.Strategy:
            if strat == st.Strategy.SINGLE: continue
            sh = st.param_shardings(specs, shapes, mesh, strat)
            p = jax.device_put(params, sh)
            pb = st.phase_boundary_fn(strat, mesh)
            losses[strat.value] = float(jax.jit(lambda p: S.forward(p, cfg, batch, phase_boundary=pb)[0])(p))
        print(json.dumps(losses))
        """
    )
    losses = _run(code)
    vals = list(losses.values())
    assert max(vals) - min(vals) < 1e-3, losses


def test_pipeline_equals_sequential_and_grad():
    code = PREAMBLE + textwrap.dedent(
        """
        from repro.models import lstm
        from repro.models.common import Initializer
        from repro.core import pipeline as pl
        mesh = make_mesh((2, 4), ("data", "model"))
        ini = Initializer(jax.random.key(0))
        L, e, h, B, S = 8, 24, 32, 8, 13
        params, _ = lstm.init_stacked_lstm(ini, "enc", L, e, h)
        x = jax.random.normal(jax.random.key(1), (B, S, e), jnp.float32)
        ref = np.array(lstm.run_stacked_lstm(params, x)[0])
        gref = jax.grad(lambda p: lstm.run_stacked_lstm(p, x)[0].sum())(params)
        with jax.set_mesh(mesh):
            stacked, _ = pl.stack_pipeline_params(params, 4)  # 2 layers / stage
            out = np.array(jax.jit(lambda st_, xx: pl.pipeline_lstm(mesh, st_, xx, in_dim=e))(stacked, x))
            g = jax.jit(jax.grad(lambda st_: pl.pipeline_lstm(mesh, st_, x, in_dim=e).sum()))(stacked)
            gs = float(jnp.abs(g["wx"]).sum())
        gerr = 0.0
        for li, pref in enumerate(gref):
            s_, l_ = li // 2, li % 2
            for name in ("wx", "wh", "b"):
                n = pref[name].shape[0]
                gerr = max(gerr, float(jnp.abs(g[name][s_, l_, :n] - pref[name]).max()))
        print(json.dumps({"err": float(np.abs(out - ref).max()), "gsum": gs, "gerr": gerr}))
        """
    )
    res = _run(code)
    assert res["err"] < 1e-5
    assert res["gsum"] > 0
    assert res["gerr"] < 2e-4, res  # the gate-stash backward = autodiff of the scans


def test_pipeline_microbatched_wavefront_matches_sequential():
    """micro_batches=k interleaves k slices through ONE wavefront on a real
    4-stage pipeline: outputs/grads match the sequential reference and the
    traced scan runs exactly k*S + NS - 1 ticks (bubble paid once per step,
    not once per microbatch)."""
    code = PREAMBLE + textwrap.dedent(
        """
        from repro.models import lstm
        from repro.models.common import Initializer
        from repro.core import pipeline as pl
        from repro.core.plan import WavefrontSchedule
        mesh = make_mesh((2, 4), ("data", "model"))
        ini = Initializer(jax.random.key(0))
        L, e, h, B, S = 8, 24, 32, 8, 13
        params, _ = lstm.init_stacked_lstm(ini, "enc", L, e, h)
        x = jax.random.normal(jax.random.key(1), (B, S, e), jnp.float32)
        ref = np.array(lstm.run_stacked_lstm(params, x)[0])

        def scan_lengths(obj, out):
            jaxpr = getattr(obj, "jaxpr", obj)
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "scan":
                    out.append(eqn.params["length"])
                for v in eqn.params.values():
                    vs = v if isinstance(v, (tuple, list)) else (v,)
                    for u in vs:
                        if hasattr(u, "eqns") or hasattr(u, "jaxpr"):
                            scan_lengths(u, out)
            return out

        res = {}
        with jax.set_mesh(mesh):
            stacked, _ = pl.stack_pipeline_params(params, 4)  # 2 layers / stage
            for k in (2, 4):
                fn = lambda st_, xx: pl.pipeline_lstm(mesh, st_, xx, in_dim=e, micro_batches=k)
                out = np.array(jax.jit(fn)(stacked, x))
                g = jax.jit(jax.grad(lambda st_: fn(st_, x).sum()))(stacked)
                lengths = scan_lengths(jax.make_jaxpr(fn)(stacked, x), [])
                sched = WavefrontSchedule(seq_len=S, num_stages=4, micro_batches=k)
                res[k] = {
                    "err": float(np.abs(out - ref).max()),
                    "gsum": float(jnp.abs(g["wx"]).sum()),
                    "ticks_ok": int(lengths.count(sched.ticks) == 1),
                    "naive_absent": int(sched.naive_ticks not in lengths),
                }
        print(json.dumps(res))
        """
    )
    res = _run(code)
    for k, r in res.items():
        assert r["err"] < 1e-5, (k, r)
        assert r["gsum"] > 0, (k, r)
        assert r["ticks_ok"] == 1, (k, r)  # ONE wavefront of k*S + NS - 1 ticks
        assert r["naive_absent"] == 1, (k, r)


def _schedule_grad_errors(mesh_shape, layers, cases, stage_kernel="jnp") -> dict:
    """Run ``pipeline_lstm`` on a ``mesh_shape`` ("data", "model") mesh of
    host devices for each (schedule, micro_batches, virtual_stages) case
    and return, per case, its worst output, parameter-grad and input-grad
    gaps to autodiff of the plain ``run_stacked_lstm``."""
    code = PREAMBLE + textwrap.dedent(
        f"""
        from repro.models import lstm
        from repro.models.common import Initializer
        from repro.core import pipeline as pl
        mesh_shape, L, cases, sk = {mesh_shape!r}, {layers!r}, {cases!r}, {stage_kernel!r}
        """
    ) + textwrap.dedent(
        """
        mesh = make_mesh(mesh_shape, ("data", "model"))
        NS = mesh_shape[1]
        ini = Initializer(jax.random.key(0))
        e, h, B, S = 24, 32, 8, 13
        params, _ = lstm.init_stacked_lstm(ini, "enc", L, e, h)
        x = jax.random.normal(jax.random.key(1), (B, S, e), jnp.float32)
        ref_y = lstm.run_stacked_lstm(params, x)[0]
        w = jax.random.normal(jax.random.key(2), ref_y.shape, jnp.float32)
        gref, gxref = jax.grad(
            lambda p, xx: (lstm.run_stacked_lstm(p, xx)[0] * w).sum(), argnums=(0, 1)
        )(params, x)
        res = {}
        with jax.set_mesh(mesh):
            stacked, _ = pl.stack_pipeline_params(params, NS)
            Lp = L // NS
            for sched, k, vs in cases:
                fn = lambda st_, xx: pl.pipeline_lstm(
                    mesh, st_, xx, in_dim=e, micro_batches=k,
                    schedule=sched, virtual_stages=vs, stage_kernel=sk)
                y = jax.jit(fn)(stacked, x)
                g, gx = jax.jit(jax.grad(
                    lambda st_, xx: (fn(st_, xx) * w).sum(), argnums=(0, 1)))(stacked, x)
                gerr = 0.0
                for li, pref in enumerate(gref):
                    s_, l_ = li // Lp, li % Lp
                    gerr = max(gerr, float(jnp.abs(g["wh"][s_, l_] - pref["wh"]).max()))
                    gerr = max(gerr, float(jnp.abs(g["b"][s_, l_] - pref["b"]).max()))
                    nwx = pref["wx"].shape[0]
                    gerr = max(gerr, float(jnp.abs(g["wx"][s_, l_, :nwx] - pref["wx"]).max()))
                res[f"{sched}_k{k}"] = {
                    "yerr": float(jnp.abs(y - ref_y).max()),
                    "gerr": gerr,
                    "gxerr": float(jnp.abs(gx - gxref).max()),
                }
        print(json.dumps(res))
        """
    )
    return _run(code, devices=mesh_shape[0] * mesh_shape[1])


def _assert_schedule_grads(res: dict):
    for name, r in res.items():
        assert r["yerr"] < 1e-5, (name, r)
        assert r["gerr"] < 2e-4, (name, r)
        assert r["gxerr"] < 1e-4, (name, r)


# every schedule kind, at 1 and 2 microbatches (interleaved with 2 chunks)
_ALL_KINDS = [(kind, k, 2 if kind == "interleaved" else 1)
              for kind in ("gpipe", "1f1b", "zerobubble", "interleaved") for k in (1, 2)]


def test_pipeline_schedule_grads_match_sequential_multistage():
    """The schedule-driven custom-vjp backward on a REAL 4-stage pipeline:
    for gpipe and 1f1b at k in (1, 2, 4), and zerobubble and interleaved
    (v=2, 1 layer/chunk) at k in (1, 2, 4), outputs AND parameter/input
    grads match the sequential reference: the mirrored backward wavefront's
    ppermute chain, the interleaved ring, the per-group recompute and the
    backward from its gate stash are numerically exact."""
    cases = _ALL_KINDS + [(kind, 4, 2 if kind == "interleaved" else 1)
                          for kind in ("gpipe", "1f1b", "zerobubble", "interleaved")]
    _assert_schedule_grads(_schedule_grad_errors((2, 4), 8, cases))


@pytest.mark.parametrize("mesh_shape,layers,stage_kernel", [
    pytest.param((1, 4), 8, "jnp", id="hybrid14-jnp"),
    pytest.param((1, 4), 8, "pallas", id="hybrid14-pallas"),
    pytest.param((1, 1), 4, "jnp", id="single-jnp"),
    pytest.param((1, 1), 4, "pallas", id="single-pallas"),
])
def test_pipeline_gate_stash_grads_match_autodiff(mesh_shape, layers, stage_kernel):
    """The backward from the recompute pass's stash — the jnp cell's
    stashed gates, or the fused Pallas cell's rebuilt ones — equals
    autodiff of the plain ``run_stacked_lstm`` for every schedule kind at
    k in (1, 2): on the (1, 4) hybrid mesh of four host devices (2 layers
    a stage, the four-chip layout) and on one device (all layers in one
    stage)."""
    _assert_schedule_grads(_schedule_grad_errors(mesh_shape, layers, _ALL_KINDS, stage_kernel))


def test_train_step_plan_microbatched_pipeline_runs_sharded():
    """End-to-end: a jit'd hybrid train step under ExecutionPlan(pipeline,
    micro_batches=2, overlap) on the (2, 4) mesh — losses finite and equal
    to the plain single-batch hybrid step."""
    code = PREAMBLE + textwrap.dedent(
        """
        import dataclasses
        from repro.core.plan import ExecutionPlan
        from repro.models import seq2seq as S
        from repro.optim import adam
        from repro.train.trainer import init_train_state, make_train_step
        # model axis of 2: the smoke config's 2 LSTM layers -> 1 layer/stage;
        # fp32 so differently-lowered schedules agree to 1e-3 (bf16 ulp ~0.03)
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), dropout=0.0, dtype="float32")
        params, specs = S.init_seq2seq(jax.random.key(0), cfg)
        B, M, N = 16, 12, 10
        batch = {
            "src": jax.random.randint(jax.random.key(1), (B, M), 3, cfg.vocab_size),
            "tgt_in": jax.random.randint(jax.random.key(2), (B, N), 3, cfg.vocab_size),
            "tgt_out": jax.random.randint(jax.random.key(3), (B, N), 3, cfg.vocab_size),
            "src_mask": jnp.ones((B, M), bool), "tgt_mask": jnp.ones((B, N), bool)}
        losses = {}
        with jax.set_mesh(mesh):
            for name, plan in [
                ("ref", ExecutionPlan(strategy=st.Strategy.HYBRID, mesh=mesh)),
                ("pipe_k2", ExecutionPlan(strategy=st.Strategy.HYBRID, mesh=mesh, micro_batches=2, use_pipeline=True)),
                ("accum_k2_ov", ExecutionPlan(strategy=st.Strategy.HYBRID, mesh=mesh, micro_batches=2, overlap=True)),
            ]:
                step, _, _ = make_train_step(cfg, adam(), plan=plan)
                stt = init_train_state(params, adam())
                stt, m = step(stt, batch, 1.0, jax.random.key(0))
                losses[name] = float(m["loss"])
        print(json.dumps(losses))
        """
    )
    losses = _run(code)
    vals = list(losses.values())
    assert all(math.isfinite(v) for v in vals), losses
    assert max(vals) - min(vals) < 1e-3, losses


def test_hybrid_full_forward_backward_transformer():
    code = PREAMBLE + textwrap.dedent(
        """
        from repro.models import transformer as T
        mesh = make_mesh((2, 4), ("data", "model"))
        out = {}
        for arch in ["qwen3-1.7b", "qwen3-moe-30b-a3b"]:
            cfg = get_config(arch, smoke=True)
            params, specs = T.init_lm(jax.random.key(0), cfg)
            shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            B, S = 8, 32
            toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
            labels = jnp.roll(toks, -1, 1); mask = jnp.ones((B, S), bool)
            vals = []
            for strat in (st.Strategy.DATA, st.Strategy.HYBRID, st.Strategy.HYBRID_OPT):
                sh = st.param_shardings(specs, shapes, mesh, strat)
                p = jax.device_put(params, sh)
                pb = st.phase_boundary_fn(strat, mesh)
                ep = cfg.moe is not None and strat != st.Strategy.DATA
                ctx = T.RunCtx(mode="train", mesh=mesh if ep else None,
                               ep_axis="model" if ep else None, data_axes=st.data_axes(mesh))
                def loss_fn(p):
                    return T.forward_train(p, cfg, toks, labels, mask, ctx=ctx, phase_boundary=pb)[0]
                l, g = jax.jit(jax.value_and_grad(loss_fn))(p)
                assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))
                vals.append(float(l))
            out[arch] = vals
        print(json.dumps(out))
        """
    )
    out = _run(code)
    for arch, vals in out.items():
        # MoE EP vs global dispatch may drop different tokens at tiny
        # capacities; dense must agree tightly.
        tol = 0.2 if "moe" in arch else 1e-3
        assert max(vals) - min(vals) < tol, (arch, vals)


def test_moe_ep_equals_global_when_capacity_ample():
    code = PREAMBLE + textwrap.dedent(
        """
        import functools, dataclasses
        from repro.models import moe
        from repro.models.common import Initializer
        from repro.configs.base import MoEConfig
        mesh = make_mesh((2, 4), ("data", "model"))
        m = MoEConfig(num_experts=8, top_k=2, d_ff_expert=16, capacity_factor=64.0)
        ini = Initializer(jax.random.key(0))
        p, _ = moe.init_moe(ini, "moe", 32, m)
        T_, d = 64, 32
        x = jax.random.normal(jax.random.key(1), (T_, d), jnp.float32)
        y_ref, aux_ref = moe.apply_moe(p, x, m)
        def shard_fn(xl, router, w1, wg, w2):
            pl = {"router": router, "w1": w1, "wg": wg, "w2": w2}
            return moe.apply_moe_ep(pl, xl, m, "silu", axis="model",
                                    stat_axes=("data", "model"))
        y_ep, aux_ep = jax.jit(jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(("data", "model"), None), P(None, None), P("model"), P("model"), P("model")),
            out_specs=(P(("data", "model"), None), P())))(x, p["router"], p["w1"], p["wg"], p["w2"])
        err = float(jnp.abs(y_ep - y_ref).max())
        print(json.dumps({"err": err, "aux_ref": float(aux_ref), "aux_ep": float(aux_ep)}))
        """
    )
    res = _run(code)
    assert res["err"] < 1e-4, res
    assert abs(res["aux_ref"] - res["aux_ep"]) < 1e-4


def test_pinned_prefill_matches_unpinned():
    """§Perf pair-2 variant: residual/attention pinning + shard_map'd
    prefill attention is a LAYOUT change only — logits must match."""
    code = PREAMBLE + textwrap.dedent(
        """
        from repro.serve.engine import prefill_fn
        from repro.models import transformer as T
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("glm4-9b", smoke=True)
        params, _ = T.init_lm(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (4, 256), 0, cfg.vocab_size)
        with jax.set_mesh(mesh):
            base = prefill_fn(cfg, strat=st.Strategy.HYBRID, mesh=mesh)(params, toks)[0]
            pinned = prefill_fn(cfg, strat=st.Strategy.HYBRID, mesh=mesh,
                                pin_residual=True, q_chunk=64)(params, toks)[0]
        err = float(jnp.abs(base - pinned).max())
        scale = float(jnp.abs(base).max())
        print(json.dumps({"err": err, "scale": scale}))
        """
    )
    res = _run(code)
    # Pinning moves the MLP down-proj from one full-K dot (GSPMD's
    # batch-replicated fallback) to ff-split partials + bf16 all-reduce —
    # the standard TP contraction. bf16 partial-sum reassociation costs
    # ~1% relative on random-init logits; bound at 2%.
    assert res["err"] < 2e-2 * max(res["scale"], 1.0), res


def test_slstm_shard_map_matches_plain_with_grads():
    """§Perf pair-1 iter-4: shard_map'd sLSTM must match the plain scan in
    values AND parameter grads (the boundary psum-of-sum equals the per-step
    sum-of-psums it replaces)."""
    code = PREAMBLE + textwrap.dedent(
        """
        from repro.models import xlstm
        from repro.models.common import Initializer
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("xlstm-350m", smoke=True)
        ini = Initializer(jax.random.key(0))
        p, _ = xlstm.init_slstm(ini, "s", cfg)
        x = jax.random.normal(jax.random.key(1), (8, 24, cfg.d_model), jnp.float32)
        def loss_plain(pp):
            return xlstm.apply_slstm(pp, cfg, x)[0].sum()
        def loss_sm(pp):
            return xlstm.apply_slstm_shard_map(mesh, pp, cfg, x, ("data", "model"))[0].sum()
        with jax.set_mesh(mesh):
            l1, g1 = jax.jit(jax.value_and_grad(loss_plain))(p)
            l2, g2 = jax.jit(jax.value_and_grad(loss_sm))(p)
        gerr = max(float(jnp.abs(a - b).max()) for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
        print(json.dumps({"lerr": abs(float(l1) - float(l2)), "gerr": gerr}))
        """
    )
    res = _run(code)
    assert res["lerr"] < 1e-3, res
    assert res["gerr"] < 1e-3, res


def test_batch_shard_backbone_matches_plain_loss_and_grads():
    """§Perf pair-3: the shard_map'd batch-parallel LSTM backbone must give
    the same loss and grads as the plain stacked scan (boundary psum-of-sum
    == per-step sum-of-psums)."""
    code = PREAMBLE + textwrap.dedent(
        """
        import dataclasses
        from repro.models import seq2seq as S
        from repro.core.pipeline import batch_shard_backbone
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), dropout=0.0)
        params, specs = S.init_seq2seq(jax.random.key(0), cfg)
        B, M, N = 8, 12, 10
        batch = S.Seq2SeqBatch(
            src=jax.random.randint(jax.random.key(1), (B, M), 0, cfg.vocab_size),
            tgt_in=jax.random.randint(jax.random.key(2), (B, N), 0, cfg.vocab_size),
            tgt_out=jax.random.randint(jax.random.key(3), (B, N), 0, cfg.vocab_size),
            src_mask=jnp.ones((B, M), bool), tgt_mask=jnp.ones((B, N), bool))
        bb = batch_shard_backbone(mesh, ("data", "model"))
        with jax.set_mesh(mesh):
            l1, g1 = jax.jit(jax.value_and_grad(lambda p: S.forward(p, cfg, batch)[0]))(params)
            l2, g2 = jax.jit(jax.value_and_grad(lambda p: S.forward(p, cfg, batch, backbone=bb)[0]))(params)
        gerr = max(float(jnp.abs(a - b).max()) for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
        # a batch the 8 shards cannot divide must raise, not silently run
        # the unsharded path with a different collective structure
        try:
            bb([], jnp.zeros((6, 4, 8)), None)
            divis_err = "missing"
        except ValueError as e:
            divis_err = "divisible" if "divisible" in str(e) else str(e)
        # ... and the plan's validate_batch must reject the SAME batch up
        # front (the other side of the seam pinned in test_plan.py)
        from repro.core.plan import ExecutionPlan
        from repro.core.strategy import Strategy
        try:
            ExecutionPlan(strategy=Strategy.DATA, mesh=mesh).validate_batch(6)
            plan_err = "missing"
        except ValueError as e:
            plan_err = "shards" if "shards" in str(e) else str(e)
        print(json.dumps({"lerr": abs(float(l1) - float(l2)), "gerr": gerr,
                          "divis_err": divis_err, "plan_err": plan_err}))
        """
    )
    res = _run(code)
    assert res["lerr"] < 1e-4, res
    assert res["gerr"] < 1e-3, res
    assert res["divis_err"] == "divisible", res
    assert res["plan_err"] == "shards", res


def test_cache_shardings_resolve():
    code = PREAMBLE + textwrap.dedent(
        """
        from repro.models import transformer as T
        from repro.serve.engine import cache_shardings
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("glm4-9b", smoke=True)
        cache = jax.eval_shape(lambda: T.init_cache(cfg, 8, 64, None))
        sh = cache_shardings(cfg, cache, mesh)
        specs = [s.spec for e in sh.entries for s in (e if isinstance(e, tuple) else jax.tree.leaves(e))]
        print(json.dumps({"n": len(specs), "first": str(specs[0])}))
        """
    )
    res = _run(code)
    assert res["n"] > 0


def test_attend_shard_map_flat_layout_falls_back_batch_only():
    """Regression (§Perf pair-2 sweep failure): for the flat q layout the
    q 'KV' dim is really H while k/v keep true KV — head sharding must not
    be attempted; batch-only shard_map must still match plain attention."""
    code = PREAMBLE + textwrap.dedent(
        """
        from repro.models import attention as A
        mesh = make_mesh((2, 4), ("data", "model"))
        B, S, KV, D = 4, 64, 2, 16
        H = 8  # flat layout: q carries H heads, kv repeat per group inside
        q = jax.random.normal(jax.random.key(0), (B, S, H, 1, D), jnp.float32)
        k = jax.random.normal(jax.random.key(1), (B, S, KV, D), jnp.float32)
        v = jax.random.normal(jax.random.key(2), (B, S, KV, D), jnp.float32)
        ref = A.chunked_attention(q, k, v, causal=True, q_chunk=32, kv_chunk=32)
        with jax.set_mesh(mesh):
            got = jax.jit(lambda q, k, v: A.attend_shard_map(
                mesh, q, k, v, causal=True, q_chunk=32, kv_chunk=32))(q, k, v)
        err = float(jnp.abs(got - ref).max())
        # grouped layout for comparison: KV=4 divides nothing, G=2... use H=8 grouped
        q2 = q.reshape(B, S, KV, H // KV, D)
        ref2 = A.chunked_attention(q2, k, v, causal=True, q_chunk=32, kv_chunk=32)
        with jax.set_mesh(mesh):
            got2 = jax.jit(lambda q, k, v: A.attend_shard_map(
                mesh, q, k, v, causal=True, q_chunk=32, kv_chunk=32))(q2, k, v)
        err2 = float(jnp.abs(got2 - ref2).max())
        print(json.dumps({"flat_err": err, "grouped_err": err2}))
        """
    )
    res = _run(code)
    assert res["flat_err"] < 1e-5, res
    assert res["grouped_err"] < 1e-5, res


def test_local_mesh_hybrid_matches_single_four_devices():
    """CPU rehearsal of ``chip_smoke.py --chips 4`` on 4 forced host
    devices: ``make_local_mesh`` (the ``launch/train.py --mesh local``
    mesh) is (data=1, model=4), and the paper's hybrid on it, one wavefront
    stage per device, gives the same first-step gradient norms, leaf by
    leaf, and trains to the same losses as SINGLE on one device at smoke
    widths (4 layers so each stage holds one)."""
    code = textwrap.dedent(
        f"""
        import dataclasses, json, sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        from repro.configs import get_config
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh()
        cfg = dataclasses.replace(get_config("seq2seq-rnn", smoke=True), num_layers=4)
        recs = cs.layout_phase(cfg, cs.CompileCounter(), mesh, steps=3, batch=8, seq=12, seed=0)
        out = {{r["run"]: r["losses"] for r in recs}}
        out["grad_norms"] = {{r["run"]: r["grad_norms"] for r in recs}}
        out["rtol"] = {{"grad": cs.GRAD_NORM_RTOL, "loss": cs.LAYOUT_LOSS_RTOL}}
        out["mesh"] = dict(zip(mesh.axis_names, map(int, mesh.devices.shape)))
        print(json.dumps(out))
        """
    )
    res = _run(code, devices=4)
    assert res["mesh"] == {"data": 1, "model": 4}
    hybrid, single = res["hybrid-local"], res["single-dev0"]
    assert len(hybrid) == len(single) == 3
    for a, b in zip(hybrid, single):
        assert abs(a - b) <= res["rtol"]["loss"] * abs(b), (hybrid, single)
    gh, gs = res["grad_norms"]["hybrid-local"], res["grad_norms"]["single-dev0"]
    assert gh.keys() == gs.keys() and len(gs) == 4 * 2 * 3 + 5
    for leaf, b in gs.items():
        assert b > 0 and abs(gh[leaf] - b) <= res["rtol"]["grad"] * b, (leaf, gh[leaf], b)

"""Stacked-LSTM encoder/decoder with Luong attention (Ono et al. 2019;
Luong et al. 2015): how the benchmark builds the program under test from a
configuration file, the plain reference it is checked against, and the
model FLOPs the utilization metrics divide by.

Configuration keys read here: ``num_layers``, ``hidden_size``,
``emb_size``, ``vocab_size``, ``input_feeding``, ``dropout``,
``compute_dtype``, ``matmul_precision``, ``optimizer`` (``lr``, ``b1``,
``b2``, ``eps``, ``clip_norm``) and ``layout`` (``strategy``, ``mesh`` as
``{"data": d, "model": m}``, ``wavefront``, ``stage_kernel``).  The
program runs under ``jax.default_matmul_precision(matmul_precision)``,
which ``program_context`` gives.

The reference imports nothing of the program.  It draws the weights from
the seed by the same recipe as the program's initializer (a normal draw
per parameter, keyed by folding the parameter's path hash into the seed's
key, scaled by fan-in; embeddings at 0.02; zero biases), which is why the
benchmark runs with ``PYTHONHASHSEED=0``.  It computes in float32 at the
matmul precision the configuration states (``matmul_precision``; ``high``
is the MXU's three-pass bfloat16 product, emulated so that it reads the
same on every backend: a ``highest`` configuration's control), runs the
LSTMs layer by layer and through the padding as the program's training
forward does, takes the loss as the masked mean cross-entropy over the
batch, and sums gradients over blocks of rows so that a full batch fits
beside nothing else.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = {"default": jax.lax.Precision.DEFAULT, "highest": jax.lax.Precision.HIGHEST}
# top-level parameter groups that form the data-parallel attention-softmax
# head (the rest is the LSTM backbone)
HEAD_GROUPS = ("head",)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def program_context(conf: dict):
    """The context the program is traced and run under: the matmul
    precision the configuration states."""
    return jax.default_matmul_precision(conf["matmul_precision"])


def program(conf: dict, devices):
    """``(cfg, plan, optimizer, clip_norm)`` of the repository's training
    path for this configuration on ``devices``."""
    from repro.configs import get_config
    from repro.core.plan import ExecutionPlan
    from repro.launch.mesh import make_mesh
    from repro.optim import adam

    dt = conf["compute_dtype"]
    cfg = dataclasses.replace(
        get_config("seq2seq-rnn"),
        num_layers=conf["num_layers"], d_model=conf["hidden_size"], emb_size=conf["emb_size"],
        vocab_size=conf["vocab_size"], input_feeding=conf["input_feeding"], dropout=conf["dropout"], dtype=dt,
    )
    lay = conf["layout"]
    shape = (lay["mesh"]["data"], lay["mesh"]["model"])
    mesh = make_mesh(shape, ("data", "model"), devices=list(devices)[: shape[0] * shape[1]])
    plan = ExecutionPlan(
        strategy=lay["strategy"], mesh=mesh, use_pipeline=lay["wavefront"],
        stage_kernel=lay["stage_kernel"], compute_dtype=dt,
    )
    o = conf["optimizer"]
    return cfg, plan, adam(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"]), o["clip_norm"]


def program_init(cfg, plan):
    """``(init, specs)``: ``init(key)`` makes the program's parameters on
    the device in one jitted call, laid out as the plan shards them."""
    from repro.models import seq2seq as s2s

    box = {}

    def init(key):
        params, box["specs"] = s2s.init_seq2seq(key, cfg)
        return params

    shapes = jax.eval_shape(init, jax.random.key(0))
    return jax.jit(init, out_shardings=plan.param_shardings(box["specs"], shapes)), box["specs"]


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------


def flops_per_token(conf: dict) -> dict:
    """Forward multiply-add FLOPs (2 per MAC) per real token: ``src`` per
    source token (encoder), ``tgt`` per target token (decoder, eq. 1-5
    projections), ``tgt_src`` per target token per source token (scores
    and context, eq. 1-3).  Each is counted once: not the recomputation of
    the wavefront's backward, nor the input-feeding decoder's second pass
    over the head."""
    h, e, v, n = conf["hidden_size"], conf["emb_size"], conf["vocab_size"], conf["num_layers"]
    lstm = lambda d_in: 2 * (d_in + h) * 4 * h  # noqa: E731 — x @ wx + h @ wh
    enc = lstm(e) + (n - 1) * lstm(h)
    dec = lstm(e + (h if conf["input_feeding"] else 0)) + (n - 1) * lstm(h)
    head = 2 * h * h + 2 * (2 * h) * h + 2 * h * v  # W_alpha, W_c, F_c
    return {"src": enc, "tgt": dec + head, "tgt_src": 4 * h}


def model_flops(conf: dict, src_len: np.ndarray, tgt_len: np.ndarray) -> float:
    """Forward FLOPs of one batch of pairs with these real lengths."""
    f = flops_per_token(conf)
    src_len, tgt_len = np.asarray(src_len, np.float64), np.asarray(tgt_len, np.float64)
    return float(f["src"] * src_len.sum() + f["tgt"] * tgt_len.sum() + f["tgt_src"] * (src_len * tgt_len).sum())


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _key(key, path: str):
    return jax.random.fold_in(key, hash(path) & 0x7FFFFFFF)


def _normal(key, path: str, shape, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(max(shape[-2] if len(shape) >= 2 else shape[-1], 1))
    return scale * jax.random.normal(_key(key, path), shape, jnp.float32)


def reference_init(conf: dict, key):
    h, e, v, n = conf["hidden_size"], conf["emb_size"], conf["vocab_size"], conf["num_layers"]

    def stack(name, d0):
        layers = []
        for li in range(n):
            d = d0 if li == 0 else h
            p = f"{name}.l{li}"
            layers.append({
                "wx": _normal(key, p + ".wx", (d, 4, h), d**-0.5),
                "wh": _normal(key, p + ".wh", (h, 4, h), h**-0.5),
                "b": jnp.zeros((4, h), jnp.float32),
            })
        return layers

    return {
        "src_emb": {"table": _normal(key, "src_emb", (v, e), 0.02)},
        "tgt_emb": {"table": _normal(key, "tgt_emb", (v, e), 0.02)},
        "encoder": stack("enc", e),
        "decoder": stack("dec", e + (h if conf["input_feeding"] else 0)),
        "head": {
            "w_alpha": _normal(key, "w_alpha", (h, h)),
            "w_c": _normal(key, "w_c", (2 * h, h)),
            "f_c": _normal(key, "f_c", (h, v)),
        },
    }


def _bf16(x):
    """``x`` cut to bfloat16 by dropping its low 16 bits (towards zero), kept
    in float32: the split the MXU's multi-pass products make.  Done on the
    bits, which XLA cannot drop as excess precision."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _three_pass(spec: str, a, b):
    """``einsum(spec, a, b)`` as the MXU's three-pass product of each
    float32's bfloat16 split, hi*hi + hi*lo + lo*hi, summed in float32;
    its gradients are three-pass products too, as the MXU's are."""
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    exact = functools.partial(jnp.einsum, spec, precision=jax.lax.Precision.HIGHEST)
    return exact(ah, bh) + exact(ah, bl) + exact(al, bh)


def _three_pass_fwd(spec, a, b):
    return _three_pass(spec, a, b), (a, b)


def _three_pass_bwd(spec, res, g):
    # every index of an operand is in the other or the output, so each
    # gradient is one einsum of the output's gradient with the other operand
    a, b = res
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    return _three_pass(f"{out},{sb}->{sa}", g, b), _three_pass(f"{sa},{out}->{sb}", a, g)


_three_pass.defvjp(_three_pass_fwd, _three_pass_bwd)


def _mm(spec: str, a, b, prec: str):
    """``einsum(spec, a, b)`` at the named precision: ``default``,
    ``highest``, or ``high``, the three-pass product (``_three_pass``)."""
    if prec == "high":
        return _three_pass(spec, a, b)
    return jnp.einsum(spec, a, b, precision=PRECISION[prec])


def _cell(p, x, h, c, prec):
    gates = _mm("bi,igh->bgh", x, p["wx"], prec) + _mm("bj,jgh->bgh", h, p["wh"], prec) + p["b"]
    i, f, g, o = gates[:, 0], gates[:, 1], gates[:, 2], gates[:, 3]
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


def _layer(p, xs, prec):
    """xs [B, T, d] -> hs [B, T, h], from zero state, through every position."""
    B, hid = xs.shape[0], p["wh"].shape[0]

    def step(carry, x):
        h, c = _cell(p, x, *carry, prec)
        return (h, c), h

    z = jnp.zeros((B, hid), jnp.float32)
    return jax.lax.scan(step, (z, z), xs.swapaxes(0, 1))[1].swapaxes(0, 1)


def _attend(head, h, S, src_mask, prec):
    """h [B, N, h] decoder states against S [B, M, h]: Hc [B, N, h] (eq. 1-4)."""
    scores = _mm("bnk,bmk->bnm", _mm("bnh,hk->bnk", h, head["w_alpha"], prec), S, prec)
    alpha = jax.nn.softmax(jnp.where(src_mask[:, None, :], scores, -1e30), axis=-1)
    C = _mm("bnm,bmh->bnh", alpha, S, prec)
    return jnp.tanh(_mm("bnk,kh->bnh", jnp.concatenate([h, C], -1), head["w_c"], prec))


def _loss_sum(conf: dict, params, batch):
    """Summed masked cross-entropy of a block of rows, and its token count."""
    prec = conf["matmul_precision"]
    S = params["src_emb"]["table"][batch["src"]]
    for p in params["encoder"]:
        S = _layer(p, S, prec)
    y = params["tgt_emb"]["table"][batch["tgt_in"]]
    head = params["head"]
    if conf["input_feeding"]:
        B, hid = y.shape[0], conf["hidden_size"]
        z = jnp.zeros((B, hid), jnp.float32)

        def step(carry, y_t):
            states, hc = carry
            x, new = jnp.concatenate([y_t, hc], -1), []
            for p, (h, c) in zip(params["decoder"], states):
                h, c = _cell(p, x, h, c, prec)
                new.append((h, c))
                x = h
            hc = _attend(head, x[:, None], S, batch["src_mask"], prec)[:, 0]
            return (new, hc), hc

        init = ([(z, z)] * len(params["decoder"]), z)
        Hc = jax.lax.scan(step, init, y.swapaxes(0, 1))[1].swapaxes(0, 1)
    else:
        for p in params["decoder"]:
            y = _layer(p, y, prec)
        Hc = _attend(head, y, S, batch["src_mask"], prec)
    logits = _mm("bnh,hv->bnv", Hc, head["f_c"], prec)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, batch["tgt_out"][..., None], -1)[..., 0]
    mask = batch["tgt_mask"].astype(jnp.float32)
    return (nll * mask).sum(), mask.sum()


@functools.lru_cache(maxsize=None)
def _block_grad(frozen: tuple):
    conf = dict(frozen)
    return jax.jit(jax.value_and_grad(lambda p, b: _loss_sum(conf, p, b), has_aux=True))


def _rows_per_block(batch: int, limit: int = 128) -> int:
    return max(d for d in range(1, min(batch, limit) + 1) if batch % d == 0)


def reference_grads(conf: dict, params, batch: dict):
    """Mean loss over the batch's target tokens and its gradient, summed
    over blocks of rows."""
    keys = ("hidden_size", "input_feeding", "matmul_precision")
    fn = _block_grad(tuple(sorted((k, v) for k, v in conf.items() if k in keys)))
    B = batch["src"].shape[0]
    r = _rows_per_block(B)
    total, count, grads = 0.0, 0.0, None
    for i in range(0, B, r):
        block = {k: jnp.asarray(v[i:i + r]) for k, v in batch.items()}
        (s, n), g = fn(params, block)
        total, count = total + float(s), count + float(n)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / count, jax.tree.map(lambda g: g / count, grads)

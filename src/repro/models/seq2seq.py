"""The paper's model: Luong-attention Seq2Seq stacked-LSTM MT
(Ono et al. 2019, Figures 1 & 3).

Two structurally different forwards:

* ``forward_no_input_feeding`` (HybridNMT, Fig. 3): the backbone phase
  computes *all* encoder states S [B,M,H] and *all* decoder states H [B,N,H]
  first (teacher forcing supplies every target word), then the
  attention-softmax phase computes, for all steps at once::

      alpha = softmax(H^T W_a S)          (paper eq. 1-2)
      C     = alpha . S                   (eq. 3)
      Hc    = tanh(W_c [H; C])            (eq. 4)
      P     = softmax(F_c Hc)             (eq. 5)

  The ``phase_boundary`` callback is invoked on S and H between the two
  phases — this is exactly where the hybrid strategy reshards from the
  model-parallel backbone layout to the fully batch-sharded data-parallel
  layout (the paper's "intermediate results ... distributed equally").

* ``forward_input_feeding`` (baseline / HybridNMTIF, Fig. 1): the decoder
  scans over time; step t consumes [emb(y_t); Hc_{t-1}], so attention is
  computed inside the scan and no all-steps-at-once phase exists.  This is
  the serialization the paper removes.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lstm
from repro.models.common import Initializer, resolve_dtype, softmax_cross_entropy

Identity = lambda x: x


class Seq2SeqBatch(NamedTuple):
    src: jax.Array  # [B, M] int32
    tgt_in: jax.Array  # [B, N] int32 (BOS-shifted)
    tgt_out: jax.Array  # [B, N] int32 (labels)
    src_mask: jax.Array  # [B, M] bool
    tgt_mask: jax.Array  # [B, N] bool


def init_seq2seq(key: jax.Array, cfg: ModelConfig):
    ini = Initializer(key)
    h, e, v = cfg.d_model, cfg.emb_size, cfg.vocab_size
    params, specs = {}, {}
    params["src_emb"] = {"table": ini.embedding("src_emb", (v, e))}
    specs["src_emb"] = {"table": ("vocab", "embed")}
    params["tgt_emb"] = {"table": ini.embedding("tgt_emb", (v, e))}
    specs["tgt_emb"] = {"table": ("vocab", "embed")}
    params["encoder"], specs["encoder"] = lstm.init_stacked_lstm(ini, "enc", cfg.num_layers, e, h)
    dec_in = e + (h if cfg.input_feeding else 0)
    params["decoder"], specs["decoder"] = lstm.init_stacked_lstm(ini, "dec", cfg.num_layers, dec_in, h)
    # attention-softmax head (the paper's data-parallel part)
    params["head"] = {
        "w_alpha": ini.normal("w_alpha", (h, h)),
        "w_c": ini.normal("w_c", (2 * h, h)),
        "f_c": ini.normal("f_c", (h, v)),
    }
    specs["head"] = {"w_alpha": ("embed", "embed"), "w_c": ("ff", "embed"), "f_c": ("embed", "vocab")}
    return params, specs


# ---------------------------------------------------------------------------
# attention-softmax phase (paper eq. 1-5) — all decoder steps at once
# ---------------------------------------------------------------------------


def attention_softmax_head(head, S: jax.Array, H: jax.Array, src_mask: jax.Array, *, stage_kernel: str = "jnp"):
    """S [B,M,h] encoder states, H [B,N,h] decoder states ->
    (Hc [B,N,h], logits [B,N,V]).

    ``stage_kernel`` uses the training plan's vocabulary: ``jnp`` runs the
    einsum math below; ``pallas`` dispatches eq. 1-4 to the fused
    ``kernels/luong_attn`` head (eq. 5 stays a plain fp32 GEMM)."""
    dt = H.dtype
    if stage_kernel != "jnp":
        from repro.kernels.luong_attn.ops import luong_attention_fused  # local: keep import light

        Hc = luong_attention_fused(H, S, src_mask, head["w_alpha"].astype(dt), head["w_c"].astype(dt))
    else:
        scores = jnp.einsum("bnh,hk,bmk->bnm", H, head["w_alpha"].astype(dt), S)
        scores = jnp.where(src_mask[:, None, :], scores.astype(jnp.float32), -1e30)
        alpha = jax.nn.softmax(scores, axis=-1).astype(dt)  # eq. 1-2
        C = jnp.einsum("bnm,bmh->bnh", alpha, S)  # eq. 3
        Hc = jnp.tanh(jnp.einsum("bnh,hk->bnk", jnp.concatenate([H, C], -1), head["w_c"].astype(dt)))  # eq. 4
    logits = jnp.einsum("bnh,hv->bnv", Hc.astype(jnp.float32), head["f_c"].astype(jnp.float32))  # eq. 5
    return Hc, logits


# ---------------------------------------------------------------------------
# forwards
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _grads_together(tree):
    """Identity whose cotangents are released together: what the head
    sends back to the backbones waits for the head's own parameter
    gradients, the logits' last readers, so the logits are freed before
    the backbones' backward passes allocate what they stash."""
    return tree


_grads_together.defvjp(lambda tree: (tree, None), lambda _, ct: (jax.lax.optimization_barrier(ct),))


def forward_no_input_feeding(
    params,
    cfg: ModelConfig,
    batch: Seq2SeqBatch,
    *,
    dropout_rng: Optional[jax.Array] = None,
    phase_boundary: Callable = Identity,
    backbone: Callable | None = None,
    stage_kernel: str = "jnp",
):
    """HybridNMT forward.  ``backbone`` optionally overrides how the stacked
    LSTMs are executed (the wavefront pipeline substitutes here); it must map
    (lstm_params, embedded [B,S,e]) -> hidden states [B,S,h].
    ``stage_kernel`` selects the attention-softmax head compute (jnp math or
    the fused Pallas Luong kernel).
    """
    dt = resolve_dtype(cfg.dtype)
    run = backbone or (lambda ps, xs, rng: lstm.run_stacked_lstm(ps, xs, dropout_rng=rng, dropout=cfg.dropout)[0])
    with jax.named_scope("embed"):
        src_e = params["src_emb"]["table"].astype(dt)[batch.src]
        tgt_e = params["tgt_emb"]["table"].astype(dt)[batch.tgt_in]
    rng_e = rng_d = None
    if dropout_rng is not None:
        rng_e, rng_d = jax.random.split(dropout_rng)
    # ---- phase 1: model-parallel backbone (all hidden states) ----------
    with jax.named_scope("encoder"):
        S = run(params["encoder"], src_e, rng_e)  # [B, M, h]
    with jax.named_scope("decoder"):
        H = run(params["decoder"], tgt_e, rng_d)  # [B, N, h]
    # ---- reshard boundary (the paper's hybrid hand-off) ----------------
    with jax.named_scope("handoff"):
        S, H = phase_boundary(S), phase_boundary(H)
    # ---- phase 2: data-parallel attention-softmax ----------------------
    with jax.named_scope("head"):
        head, S, H = _grads_together((params["head"], S, H))
        _, logits = attention_softmax_head(head, S, H, batch.src_mask, stage_kernel=stage_kernel)
        loss, denom = softmax_cross_entropy(logits, batch.tgt_out, batch.tgt_mask)
    return loss, {"logits": logits, "denom": denom}


def forward_input_feeding(
    params,
    cfg: ModelConfig,
    batch: Seq2SeqBatch,
    *,
    dropout_rng: Optional[jax.Array] = None,
    phase_boundary: Callable = Identity,
    stage_kernel: str = "jnp",
):
    """Baseline / HybridNMTIF forward: Hc_{t-1} concatenated to the first
    decoder LSTM input (Fig. 1) — the decoder is a single serial scan."""
    dt = resolve_dtype(cfg.dtype)
    h = cfg.d_model
    B, N = batch.tgt_in.shape
    with jax.named_scope("embed"):
        src_e = params["src_emb"]["table"].astype(dt)[batch.src]
        tgt_e = params["tgt_emb"]["table"].astype(dt)[batch.tgt_in]
    with jax.named_scope("encoder"):
        S = lstm.run_stacked_lstm(params["encoder"], src_e, dropout_rng=dropout_rng, dropout=cfg.dropout)[0]
    with jax.named_scope("handoff"):
        S = phase_boundary(S)
    head = params["head"]
    dec = params["decoder"]
    states0 = [lstm.init_lstm_state(B, h) for _ in dec]

    # scoped inside the body too: what the scan hoists out of its loop keeps
    # the body's name stack, not the scan's
    @jax.named_scope("decoder")
    def step(carry, emb_t):
        states, hc_prev = carry
        x = jnp.concatenate([emb_t, hc_prev.astype(dt)], axis=-1)
        new_states = []
        hcur = x
        for p, st in zip(dec, states):
            st2, hcur = lstm.lstm_cell(p, hcur, st)
            new_states.append(st2)
        Hc, _ = attention_softmax_head(head, S, hcur[:, None, :], batch.src_mask, stage_kernel=stage_kernel)
        hc = Hc[:, 0]
        return (new_states, hc), hcur

    with jax.named_scope("decoder"):
        (states, _), Hs = jax.lax.scan(step, (states0, jnp.zeros((B, h), dt)), tgt_e.swapaxes(0, 1))
        H = Hs.swapaxes(0, 1)  # [B, N, h]
    with jax.named_scope("head"):
        _, logits = attention_softmax_head(head, S, H, batch.src_mask, stage_kernel=stage_kernel)
        loss, denom = softmax_cross_entropy(logits, batch.tgt_out, batch.tgt_mask)
    return loss, {"logits": logits, "denom": denom}


def forward(params, cfg: ModelConfig, batch: Seq2SeqBatch, **kw):
    if cfg.input_feeding:
        kw.pop("backbone", None)
        return forward_input_feeding(params, cfg, batch, **kw)
    return forward_no_input_feeding(params, cfg, batch, **kw)


# ---------------------------------------------------------------------------
# serving path: encdec_memory cache (encoder states S are the cached memory,
# the Luong attention-softmax head is the per-token decode step)
# ---------------------------------------------------------------------------


class Seq2SeqCache(NamedTuple):
    """Per-request serving state for the ``encdec_memory`` cache policy.

    The encoder states S — the paper's phase-1 output — are the cached
    "memory" a request carries; the decoder side is O(1): the stacked-LSTM
    cell states plus the input-feeding carry Hc."""

    memory: jax.Array  # [B, M_cap, h] encoder states written so far
    src_mask: jax.Array  # [B, M_cap] bool: which memory slots are real
    enc_states: tuple  # per-layer LSTMCellState — carried across encode chunks
    dec_states: tuple  # per-layer LSTMCellState
    hc: jax.Array  # [B, h] input-feeding carry (zeros when unused)
    length: jax.Array  # [] int32: source positions encoded so far


def init_seq2seq_cache(cfg: ModelConfig, batch: int, capacity: int) -> Seq2SeqCache:
    dt = resolve_dtype(cfg.dtype)
    h = cfg.d_model
    states = tuple(lstm.init_lstm_state(batch, h) for _ in range(cfg.num_layers))
    return Seq2SeqCache(
        memory=jnp.zeros((batch, capacity, h), dt),
        src_mask=jnp.zeros((batch, capacity), bool),
        enc_states=states,
        dec_states=states,
        hc=jnp.zeros((batch, h), dt),
        length=jnp.zeros((), jnp.int32),
    )


def encode_extend(params, cfg: ModelConfig, src_chunk: jax.Array, cache: Seq2SeqCache, chunk_mask=None):
    """Chunked prefill for the encdec policy: run the encoder over
    ``src_chunk`` [B, s] continuing from the carried LSTM states, write the
    resulting states into the memory at ``cache.length``.  ``chunk_mask``
    [B, s] marks real tokens (default all-real); padded positions still run
    through the LSTM (same semantics as the batched training forward) but
    are masked out of the attention memory."""
    dt = resolve_dtype(cfg.dtype)
    B, s = src_chunk.shape
    src_e = params["src_emb"]["table"].astype(dt)[src_chunk]
    h, enc_states = lstm.run_stacked_lstm(params["encoder"], src_e, states=list(cache.enc_states))
    if chunk_mask is None:
        chunk_mask = jnp.ones((B, s), bool)
    memory = jax.lax.dynamic_update_slice(cache.memory, h.astype(cache.memory.dtype), (0, cache.length, 0))
    src_mask = jax.lax.dynamic_update_slice(cache.src_mask, chunk_mask, (0, cache.length))
    return cache._replace(
        memory=memory, src_mask=src_mask, enc_states=tuple(enc_states), length=cache.length + s
    )


def init_memory_pools(cfg: ModelConfig, phys_pages: int, page_size: int):
    """Paged encdec memory: a pool of encoder-state pages plus the matching
    src_mask pages — [phys_pages, page_size, h] / [phys_pages, page_size].
    A source sentence reserves ``ceil(src_len / page_size)`` pages instead of
    a full ``max_len`` memory stripe (decode never writes the memory, so the
    reservation is exactly the prompt's length)."""
    dt = resolve_dtype(cfg.dtype)
    return (
        jnp.zeros((phys_pages, page_size, cfg.d_model), dt),
        jnp.zeros((phys_pages, page_size), bool),
    )


def paged_seq2seq_view(one: Seq2SeqCache, pools, rows: jax.Array) -> Seq2SeqCache:
    """One slot's decodable cache: gather its page rows into the contiguous
    [1, n*page, h] memory (+mask) view ``decode_step``/``encode_extend``
    already consume; ``one`` carries the per-slot LSTM states, carry and
    length with zero-capacity memory placeholders."""
    mem_pool, msk_pool = pools
    n, page = rows.shape[0], mem_pool.shape[1]
    mem = jnp.take(mem_pool, rows, axis=0).reshape(1, n * page, mem_pool.shape[2])
    msk = jnp.take(msk_pool, rows, axis=0).reshape(1, n * page)
    return one._replace(memory=mem, src_mask=msk)


def split_paged_seq2seq(new_cache: Seq2SeqCache, one: Seq2SeqCache, wp: jax.Array, page_size: int):
    """Undo :func:`paged_seq2seq_view` after an encode chunk: per-slot state
    keeps the updated LSTM carries with the zero-capacity memory placeholders
    restored, and the single written page (slot-local index ``wp``) comes out
    for the engine's scatter into the pools."""
    mem = jax.lax.dynamic_slice_in_dim(new_cache.memory, wp * page_size, page_size, axis=1)[0]
    msk = jax.lax.dynamic_slice_in_dim(new_cache.src_mask, wp * page_size, page_size, axis=1)[0]
    return new_cache._replace(memory=one.memory, src_mask=one.src_mask), (mem, msk)


def decode_step(params, cfg: ModelConfig, token: jax.Array, cache: Seq2SeqCache, *, stage_kernel: str = "jnp", pin=None):
    """One serving decode step: embed ``token`` [B], advance the decoder
    LSTM cells, run the attention-softmax head against the cached memory.
    Returns (logits [B, V], new cache).

    ``pin`` (model-axis serving): sharding constraint applied to the Luong
    context vector Hc — eq. 4's contraction psums the hidden-sharded memory
    and decoder state, and the pin marks Hc replicated right there, so the
    per-token context vector is the only value crossing the model axis
    before the vocab-sharded eq. 5 GEMM."""
    dt = resolve_dtype(cfg.dtype)
    emb = params["tgt_emb"]["table"].astype(dt)[token]
    x = jnp.concatenate([emb, cache.hc.astype(dt)], -1) if cfg.input_feeding else emb
    new_states = []
    hcur = x
    for p, st in zip(params["decoder"], cache.dec_states):
        st2, hcur = lstm.lstm_cell(p, hcur, st)
        new_states.append(st2)
    Hc, logits = attention_softmax_head(
        params["head"], cache.memory, hcur[:, None, :], cache.src_mask, stage_kernel=stage_kernel
    )
    if pin is not None:
        Hc = pin(Hc)
    return logits[:, 0], cache._replace(dec_states=tuple(new_states), hc=Hc[:, 0])


def greedy_decode(params, cfg: ModelConfig, src: jax.Array, src_mask: jax.Array, max_len: int, bos: int, eos: int):
    """Greedy search; returns [B, max_len] tokens.  Thin wrapper over the
    serving path (encode_extend + decode_step) — the same computation the
    continuous-batching engine runs per slot."""
    B, M = src.shape
    cache = init_seq2seq_cache(cfg, B, M)
    cache = encode_extend(params, cfg, src, cache, chunk_mask=src_mask)

    def step(carry, _):
        tok, cache, done = carry
        logits, cache = decode_step(params, cfg, tok, cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(done, eos, nxt)
        done = done | (nxt == eos)
        return (nxt, cache, done), nxt

    carry0 = (jnp.full((B,), bos, jnp.int32), cache, jnp.zeros((B,), bool))
    _, toks = jax.lax.scan(step, carry0, None, length=max_len)
    return toks.swapaxes(0, 1)

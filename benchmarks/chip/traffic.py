"""The one generator of training traffic: length-bucketed sentence pairs.

A traffic mix is a JSON file under ``traffic/`` that this module reads; a
new mix is a new file.  Keys:

- ``pairs_per_step``: sentence pairs in every batch (the global batch).
- ``src_len``: ``{"mean", "sigma", "min", "max"}`` of a lognormal source
  length (``mean`` is the distribution's, before clipping), rounded to
  whole tokens and clipped by rejection to ``[min, max]``.
- ``tgt_per_src``: target tokens per source token; each target has
  ``rint(tgt_per_src * source length)`` tokens (at least 1) before EOS.
- ``block``: batches per block.
- ``buckets``: ``[{"pad", "lo", "hi"}, ...]``.  A batch comes from one
  bucket: every source in it has ``lo <= len <= hi`` tokens, and source
  and target are padded to ``pad``.  How many of a block's batches come
  from each bucket follows from the length distribution
  (``block_mix``): its mass in the bucket, apportioned by largest
  remainder.

The target is the source reversed and passed through an affine
permutation of the vocabulary (the mapping of the repository's synthetic
MT task), cut or repeated to its length, plus EOS.  The stream opens with
one batch of each bucket, in the order listed, which warms every shape
up; then come blocks whose bucket order is shuffled per seed, so any
stretch of the stream holds the bucket mix to within one block.  Every
batch is drawn on the host when it is asked for.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

PAD, BOS, EOS = 0, 1, 2
N_SPECIAL = 3


def load(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    for b in spec["buckets"]:
        longest = max(1, round(spec["tgt_per_src"] * b["hi"]))
        if not (1 <= b["lo"] <= b["hi"] and b["hi"] + 1 <= b["pad"] and longest + 1 <= b["pad"]):
            raise ValueError(f"{path}: bucket {b} needs 1 <= lo <= hi, and room for hi and its target + EOS in pad")
    return spec


def _cdf(spec: dict, x: float) -> float:
    """P(source length < x) before clipping."""
    s = spec["src_len"]
    median = s["mean"] / math.exp(s["sigma"] ** 2 / 2)
    return 0.5 * (1 + math.erf(math.log(x / median) / (s["sigma"] * math.sqrt(2))))


def block_mix(spec: dict) -> list[int]:
    """Batches of each bucket in a block: the length distribution's mass in
    the bucket (clipped to ``[min, max]``), apportioned over ``block``
    batches by largest remainder."""
    s = spec["src_len"]
    mass = [_cdf(spec, min(b["hi"], s["max"]) + 0.5) - _cdf(spec, max(b["lo"], s["min"]) - 0.5)
            for b in spec["buckets"]]
    share = [spec["block"] * m / sum(mass) for m in mass]
    mix = [math.floor(x) for x in share]
    for i in sorted(range(len(share)), key=lambda i: mix[i] - share[i])[: spec["block"] - sum(mix)]:
        mix[i] += 1
    return mix


def map_tokens(t: np.ndarray, vocab: int) -> np.ndarray:
    """The synthetic translation's token mapping (gcd(7, vocab - 3) == 1)."""
    return (t - N_SPECIAL) * 7 % (vocab - N_SPECIAL) + N_SPECIAL


class PairFeed:
    """Iterator of batches ``dict(src, tgt_in, tgt_out, src_mask, tgt_mask)``
    from one seed.  ``history`` keeps, per batch handed out, its bucket pad
    and the source and target lengths (real tokens, no padding)."""

    def __init__(self, spec: dict, vocab: int, seed: int):
        self.spec, self.vocab = spec, vocab
        self.rng = np.random.default_rng(seed)
        self.history: list[tuple[int, np.ndarray, np.ndarray]] = []
        self._order = list(range(len(spec["buckets"])))
        self._block = [i for i, n in enumerate(block_mix(spec)) for _ in range(n)]

    def __iter__(self):
        return self

    def _lengths(self, lo: int, hi: int, n: int) -> np.ndarray:
        s = self.spec["src_len"]
        lo, hi = max(lo, s["min"]), min(hi, s["max"])
        out = np.empty(0, np.int64)
        while out.size < n:
            mu = np.log(s["mean"]) - s["sigma"] ** 2 / 2
            draw = np.rint(self.rng.lognormal(mu, s["sigma"], size=4 * n)).astype(np.int64)
            out = np.concatenate([out, draw[(draw >= lo) & (draw <= hi)]])
        return out[:n]

    def __next__(self) -> dict:
        if not self._order:
            self._order = list(self.rng.permutation(self._block))
        bucket = self.spec["buckets"][int(self._order.pop(0))]
        B, pad = self.spec["pairs_per_step"], bucket["pad"]
        src_len = self._lengths(bucket["lo"], bucket["hi"], B)
        tgt_len = np.maximum(1, np.rint(self.spec["tgt_per_src"] * src_len)).astype(np.int64)
        pos = np.arange(pad)[None, :]
        src_mask = pos < src_len[:, None]
        src = np.where(src_mask, self.rng.integers(N_SPECIAL, self.vocab, size=(B, pad)), PAD).astype(np.int32)
        # target position j holds source position len - 1 - j, cycling
        rev = np.take_along_axis(src, (src_len[:, None] - 1 - pos) % src_len[:, None], axis=1)
        tgt = np.where(pos < tgt_len[:, None], map_tokens(rev, self.vocab), PAD)
        tgt[np.arange(B), tgt_len] = EOS
        tgt = tgt.astype(np.int32)
        tgt_in = np.concatenate([np.full((B, 1), BOS, np.int32), tgt[:, :-1]], axis=1)
        self.history.append((pad, src_len, tgt_len + 1))
        return dict(src=src, tgt_in=tgt_in, tgt_out=tgt, src_mask=src_mask, tgt_mask=tgt != PAD)

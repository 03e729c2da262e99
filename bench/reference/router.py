"""Plain reference of the routing decision, in float64 numpy.

Query embedding: the hashed character-n-gram embedder the router is
specified with (character 3- to 5-grams of the lower-cased text between
``^`` and ``$``, blake2s into 4096 buckets, log1p counts, a Gaussian
projection to 768 drawn from ``default_rng(1234567)`` and scaled by
1/sqrt(768), L2 normalisation).

Scores: the paper's single-head cross-attention (query projection,
keys and values from the pool's model embeddings, softmax over members,
output head), the ``attn-ens`` variant's per-head outputs (mean and
population standard deviation over heads), and the cost head
de-normalised by the cost scaler and clamped at zero. Choice: argmax over
members of R2 = s * exp(-c / lambda).
"""
from __future__ import annotations

import hashlib
import math
from typing import Dict, Sequence, Tuple

import numpy as np

EMB_DIM = 768
N_BUCKETS = 4096
PROJ_SEED = 1234567


class Embedder:
    def __init__(self):
        rng = np.random.default_rng(PROJ_SEED)
        self.proj = (rng.standard_normal((N_BUCKETS, EMB_DIM))
                     .astype(np.float32).astype(np.float64)
                     / math.sqrt(EMB_DIM))

    def __call__(self, texts: Sequence[str]) -> np.ndarray:
        out = np.zeros((len(texts), EMB_DIM))
        for row, text in enumerate(texts):
            t = f"^{text.lower()}$"
            counts = np.zeros(N_BUCKETS)
            for n in range(3, 6):
                for i in range(max(0, len(t) - n + 1)):
                    h = hashlib.blake2s(t[i:i + n].encode("utf-8"),
                                        digest_size=4).digest()
                    counts[int.from_bytes(h, "little") % N_BUCKETS] += 1.0
            if counts.sum() > 0:
                counts = np.log1p(counts)
            v = counts @ self.proj
            norm = np.linalg.norm(v)
            out[row] = v / norm if norm > 0 else v
        return out


def _caster(xp, dtype):
    """numpy float64 for the reference; for the control ``jax.numpy`` in
    ``dtype``, a float8 type keeping each tensor's scale and computing on
    in bfloat16."""
    def cast(a):
        if np.dtype(dtype).itemsize > 1:
            return xp.asarray(np.asarray(a)).astype(dtype)
        a = xp.asarray(np.asarray(a, np.float32))
        top = float(xp.finfo(dtype).max)
        scale = max(float(xp.max(xp.abs(a))), 1e-30) / top
        return ((a / scale).astype(dtype).astype(xp.float32) * scale
                ).astype(xp.bfloat16)
    return cast


def _context(xp, p: Dict, q, m, dtype):
    cast = _caster(xp, dtype)

    qp = cast(q) @ cast(p["wq"])
    kp = cast(m) @ cast(p["wk"])
    vp = cast(m) @ cast(p["wv"])
    logits = (qp @ kp.T) / math.sqrt(vp.shape[-1])
    logits = logits - logits.max(axis=-1, keepdims=True)
    a = xp.exp(logits)
    a = a / a.sum(axis=-1, keepdims=True)
    return a @ vp


def scores(router: Dict, q_emb: np.ndarray, dtype=np.float64, xp=np
           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(s_mean, s_std, c) for query embeddings ``q_emb`` (B, 768), in
    ``dtype`` on the array module ``xp`` (numpy float64 for the
    reference; ``jax.numpy`` with a lower precision for the control).

    ``router`` holds host copies of ``quality`` and ``cost`` parameters,
    ``model_emb`` (K, C), ``scaler`` ({"mu", "sd"} or None) and
    ``quality_kind`` ("attn" or "attn-ens")."""
    cast = _caster(xp, dtype)

    m = router["model_emb"]
    qp = router["quality"]
    ctx = _context(xp, qp, q_emb, m, dtype)
    if router["quality_kind"] == "attn-ens":
        heads = (xp.einsum("bd,hdk->hbk", ctx, cast(qp["wo"]))
                 + cast(qp["bo"])[:, None, :])
        s, s_std = heads.mean(axis=0), heads.std(axis=0)
    else:
        s = ctx @ cast(qp["wo"]) + cast(qp["bo"])
        s_std = s * 0
    cp = router["cost"]
    c = _context(xp, cp, q_emb, m, dtype) @ cast(cp["wo"]) + cast(cp["bo"])
    sc = router["scaler"]
    if sc is not None:
        c = c * cast(sc["sd"]) + cast(sc["mu"])
    s, s_std, c = (np.asarray(a, np.float64) for a in (s, s_std, c))
    return s, s_std, np.maximum(c, 0.0)


def reward(s: np.ndarray, c: np.ndarray, lam: float) -> np.ndarray:
    return s * np.exp(-c / lam)

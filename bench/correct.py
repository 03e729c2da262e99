"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, what the
timed path produced is held against the plain references:

  score_err       router scores (quality mean, ensemble spread, cost) of
                  every scored batch, against ``reference/router.py`` on
                  the same texts: max |program - reference| over the
                  reference's largest |score|
  route_mismatch  scored requests whose routed member differs from the
                  reference's argmax of R2 at the config's lambda, where
                  the reference's reward margin exceeds twice the
                  ``score_err`` limit (closer calls are ties at that
                  precision)
  gap.<member>    for a sample of the generated requests drawn from the
                  seed (the longest prompt always in it): the widest gap
                  by which a served token's logit lies below the best
                  logit of the member's reference at that position
                  (``reference/<module>.py``, the module its file names
                  under "reference"), teacher-forced over prompt plus
                  served tokens
  bad_len         completed requests that did not get ``max_new`` tokens
  cache_mismatch  cache-served requests whose answer is not the answer
                  generated earlier for a text within the cache radius
                  (reference embeddings; radius from the same quantile of
                  the same corpus)

A control (``CONTROLS``) puts the references, computed in a lower
precision, in the program's place and reads the same numbers; the fault
``token_altered`` reads them with each sampled request's first served
token replaced by the next id, as a token altered where it is produced.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np

from bench.reference import for_member
from bench.reference import router as ref_router

CONTROLS = ("bfloat16", "float8_e4m3fn", "token_altered")
GEN_SAMPLE_PER_MEMBER = 24
REF_BATCH = 2
RADIUS_QUANTILE = 0.05
RADIUS_SAMPLE = 512


@dataclasses.dataclass
class Number:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


class Embeddings:
    """Reference embeddings, one per distinct text."""

    def __init__(self):
        self.embed = ref_router.Embedder()
        self.cache: Dict[str, np.ndarray] = {}

    def __call__(self, texts: Sequence[str]) -> np.ndarray:
        new = [t for t in dict.fromkeys(texts) if t not in self.cache]
        if new:
            for t, row in zip(new, self.embed(new)):
                self.cache[t] = row
        return np.stack([self.cache[t] for t in texts])


def reference_radius(emb: Embeddings, corpus_texts: Sequence[str]) -> float:
    """The cache radius as specified: the RADIUS_QUANTILE quantile of
    nearest-neighbour distances among the first RADIUS_SAMPLE texts of
    the router's training split."""
    x = emb(list(corpus_texts[:RADIUS_SAMPLE]))
    sq = (x * x).sum(1)
    d2 = sq[:, None] - 2 * x @ x.T + sq[None, :]
    np.fill_diagonal(d2, np.inf)
    nn = np.sqrt(np.maximum(d2.min(axis=1), 0.0))
    return float(max(np.quantile(nn, RADIUS_QUANTILE), 1e-6))


def _router_numbers(scores: List[dict], router: dict, lam: float,
                    score_limit: float, emb: Embeddings, control):
    err, mismatch = 0.0, 0
    for rec in scores:
        q = emb(rec["texts"])
        s_ref, sd_ref, c_ref = ref_router.scores(router, q)
        if control in (None, "token_altered"):
            s, sd, c = rec["s"], rec["s_std"], rec["c"]
            choices = rec.get("choices")
        else:
            s, sd, c = ref_router.scores(router, q,
                                         dtype=getattr(_jnp(), control),
                                         xp=_jnp())
            choices = np.argmax(ref_router.reward(s, c, lam), axis=-1)
        scale_s = max(float(np.max(np.abs(s_ref))), 1e-30)
        scale_c = max(float(np.max(np.abs(c_ref))), 1e-30)
        err = max(err,
                  float(np.max(np.abs(s - s_ref))) / scale_s,
                  float(np.max(np.abs(sd - sd_ref))) / scale_s,
                  float(np.max(np.abs(c - c_ref))) / scale_c)
        if choices is None:
            continue
        r = ref_router.reward(s_ref, c_ref, lam)
        srt = np.sort(r, axis=-1)
        margin = (srt[:, -1] - srt[:, -2]) / max(float(np.max(np.abs(r))),
                                                 1e-30)
        want = np.argmax(r, axis=-1)
        mismatch += int(np.sum((np.asarray(choices) != want)
                               & (margin > 2 * score_limit)))
    return err, mismatch


def _jnp():
    import jax.numpy as jnp

    return jnp


def sample_rows(gens: List[dict], n_members: int, seed: int,
                per_member: int = GEN_SAMPLE_PER_MEMBER):
    """(member, prompt, served tokens) rows: per member, the longest
    prompt and a seeded sample of the rest."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    out = []
    for mi in range(n_members):
        rows = [(mi, p, o) for g in gens if g["member"] == mi
                for p, o in zip(g["prompts"], g["outs"])]
        if not rows:
            continue
        longest = int(np.argmax([len(p) for _, p, _ in rows]))
        rest = [i for i in range(len(rows)) if i != longest]
        pick = [longest] + list(rng.permutation(rest)[:per_member - 1])
        out.extend(rows[i] for i in sorted(pick))
    return out


def _gap_numbers(rows, members: Sequence[dict], weights, control):
    """Per member, the widest gap over the sampled rows."""
    import jax.numpy as jnp

    low_dtype = (None if control in (None, "token_altered")
                 else getattr(jnp, control))
    out = {}
    for mi, member in enumerate(members):
        mine = [(p, o) for m, p, o in rows if m == mi]
        if not mine:
            continue
        ref = for_member(member)
        seq = _round_up(max(len(p) + len(o) for p, o in mine), 128)
        steps = max(len(o) for _, o in mine)
        gaps = []
        for lo in range(0, len(mine), REF_BATCH):
            chunk = mine[lo:lo + REF_BATCH]
            toks = np.zeros((REF_BATCH, seq), np.int32)
            read = np.zeros((REF_BATCH, steps), np.int32)
            for i, (p, o) in enumerate(chunk):
                full = np.concatenate([p, o[:-1]]).astype(np.int32)
                toks[i, :len(full)] = full
                read[i] = len(p) - 1 + np.minimum(np.arange(steps),
                                                  len(o) - 1)
            want = np.asarray(ref.logits_at(
                member, weights[mi], jnp.asarray(toks), jnp.asarray(read)),
                np.float64)
            if low_dtype is not None:
                low = np.asarray(ref.logits_at(
                    member, weights[mi], jnp.asarray(toks),
                    jnp.asarray(read), dtype=low_dtype))
            for i, (p, o) in enumerate(chunk):
                served = np.array(o)
                if low_dtype is not None:
                    served = np.argmax(low[i, :len(o)], axis=-1)
                elif control == "token_altered":
                    served[0] = (served[0] + 1) % want.shape[-1]
                w = want[i, :len(o)]
                gaps.extend(w.max(axis=-1) - w[np.arange(len(o)), served])
        out[member["name"]] = float(np.max(gaps))
    return out


def _cache_mismatch(completed, emb: Embeddings, radius: float) -> int:
    generated = [r for r in completed if r.leg >= 1]
    bad = 0
    for h in (r for r in completed if r.leg == 0):
        q = emb([h.text])[0]
        ok = False
        for g in generated:
            if g.finish_s > h.finish_s:
                continue
            d = float(np.linalg.norm(emb([g.text])[0] - q))
            if d <= radius * (1 + 1e-6) and np.array_equal(
                    np.asarray(g.output)[:h.max_new], np.asarray(h.output)):
                ok = True
                break
        bad += not ok
    return bad


def compare(*, members: Sequence[dict], weights, router: dict, lam: float,
            limits: Dict[str, float], scores: List[dict], gens: List[dict],
            completed, corpus_texts: Sequence[str], seed: int,
            control=None) -> List[Number]:
    """Every number compared, each beside its limit. ``control`` (one of
    ``CONTROLS``) reads that control instead of the program."""
    emb = Embeddings()
    err, mismatch = _router_numbers(scores, router, lam, limits["score_err"],
                                    emb, control)
    out = [Number("score_err", err, limits["score_err"]),
           Number("route_mismatch", mismatch, limits["route_mismatch"])]
    rows = sample_rows(gens, len(members), seed)
    for name, gap in _gap_numbers(rows, members, weights, control).items():
        out.append(Number(f"gap.{name}", gap, limits[f"gap.{name}"]))
    if control is None:
        bad = sum(len(r.output) != r.max_new for r in completed)
        out.append(Number("bad_len", bad, limits["bad_len"]))
    radius = reference_radius(emb, corpus_texts)
    out.append(Number("cache_mismatch",
                      _cache_mismatch(completed, emb, radius),
                      limits["cache_mismatch"]))
    return out


def summary_lines(numbers: Sequence[Number]) -> List[str]:
    return [f"{n.name} {n.value!r} limit {n.limit!r}"
            f"{'' if n.ok else '  FAIL'}" for n in numbers]


def as_dict(numbers: Sequence[Number]) -> Dict[str, Dict[str, float]]:
    return {n.name: {"value": n.value, "limit": n.limit} for n in numbers}


def all_ok(numbers: Sequence[Number]) -> bool:
    return all(n.ok for n in numbers)


"""The one traffic generator: a traffic mix is a data file, this reads it.

A mix (``bench/traffic/<name>.json``) gives the loop and the shapes:

  loop          "open" (requests offered at due times, Poisson gaps) or
                "closed" (``clients`` requests always outstanding)
  rate_per_s    open loop: mean arrivals per second
  clients       closed loop: requests kept outstanding
  tasks         RouterBench tasks whose texts the requests carry
  prompt_len    [lo, hi] prompt tokens, uniform
  max_new       tokens generated per request
  token_vocab   prompt ids are drawn from [0, token_vocab)
  repeat_frac   share of arrivals that repeat a text of the hot set
  hot_set       size of that hot set; picks within it are Zipf(zipf_s)

Every seed gets the same work. The texts, each text's prompt length,
the arrival times and the order of the requests are fixed, drawn once
from ``SHAPE_SEED``; ``--seed`` draws the prompt ids (and, in the
harness, the members' weights). The order is fixed too because the
program under test compiles its prefill for every new padded batch
shape: which requests share a micro-batch decides which shapes compile,
so a seed that reordered the requests would change the work, not only
the data. Prompt ids are drawn from the text and the seed, so a repeated
text carries the same tokens. Closed-loop requests come in blocks of
``clients``, the same texts and lengths in the same order for every seed.

The arrival process is the Poisson/Zipf hot-set shape of the program's
``serving/traffic.py`` (``poisson`` and ``neardup``), copied here with
the cells' real shapes so that the yardstick cannot move with the program.
"""
from __future__ import annotations

import dataclasses
import json
import zlib
from typing import List, Sequence

import numpy as np

SHAPE_SEED = 20251012


@dataclasses.dataclass
class Draft:
    """One request as the generator makes it; the harness turns it into
    the program's ``Request``."""

    text: str
    prompt: np.ndarray
    max_new: int
    due_s: float


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    lo, hi = mix["prompt_len"]
    if not 1 <= lo <= hi:
        raise ValueError(f"{path}: bad prompt_len {mix['prompt_len']}")
    return mix


def _seed_words(seed: int) -> List[int]:
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    words = [seed & 0xFFFFFFFF]
    seed >>= 32
    while seed:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
    return words


def _text_key(text: str) -> int:
    return zlib.crc32(text.encode("utf-8"))


def prompt_ids(text: str, length: int, seed: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(_seed_words(seed) + [_text_key(text)])
    return rng.integers(0, vocab, size=length).astype(np.int32)


def _lengths(n: int, lo: int, hi: int) -> np.ndarray:
    """n lengths that cover [lo, hi] evenly: the i-th of n strata."""
    span = hi - lo + 1
    return lo + ((np.arange(n) + 0.5) * span / n).astype(np.int64)


def _hot_picks(n: int, mix: dict, shape: np.random.Generator) -> np.ndarray:
    """Which arrivals repeat a hot text (index into the hot set), else -1:
    the neardup shape, as a fixed multiset."""
    frac = float(mix.get("repeat_frac", 0.0))
    n_rep = int(round(frac * n))
    picks = np.full(n, -1, np.int64)
    if n_rep:
        h = int(mix["hot_set"])
        w = 1.0 / np.arange(1, h + 1) ** float(mix.get("zipf_s", 1.0))
        picks[:n_rep] = shape.choice(h, size=n_rep, p=w / w.sum())
    return picks


def open_loop(mix: dict, texts: Sequence[str], seed: int, seconds: float,
              vocab: int) -> List[Draft]:
    """Requests due in ``[0, seconds)``: round(rate * seconds) of them."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    shape = np.random.default_rng(SHAPE_SEED)
    gaps = shape.exponential(1.0, size=n)
    picks = _hot_picks(n, mix, shape)
    n_hot = int(mix.get("hot_set", 0)) if (picks >= 0).any() else 0
    n_unique = int((picks < 0).sum()) + n_hot
    if n_unique > len(texts):
        raise ValueError(f"{n_unique} distinct texts needed, corpus has "
                         f"{len(texts)}")
    pool = list(texts[:n_unique])
    hot, fresh = pool[:n_hot], iter(pool[n_hot:])
    chosen = [hot[p] if p >= 0 else next(fresh) for p in picks]
    lo, hi = mix["prompt_len"]
    length_of = dict(zip(pool, shape.permutation(_lengths(len(pool), lo,
                                                          hi))))

    # The gaps fill the window exactly, so every seed offers n requests
    # in [0, seconds); the last one is due half a mean gap before the end.
    due = np.cumsum(gaps) / gaps.sum() * (seconds - 0.5 * seconds / n)
    order = shape.permutation(n)
    return [Draft(text=chosen[i],
                  prompt=prompt_ids(chosen[i], int(length_of[chosen[i]]),
                                    seed, vocab),
                  max_new=int(mix["max_new"]), due_s=float(due[k]))
            for k, i in enumerate(order)]


def closed_loop(mix: dict, texts: Sequence[str], seed: int, n_blocks: int,
                vocab: int) -> List[Draft]:
    """``n_blocks`` blocks of ``clients`` requests, offered in order as
    earlier ones complete (``due_s`` is set when offered); each block's
    texts in corpus order, with lengths drawn from ``SHAPE_SEED``."""
    c = int(mix["clients"])
    if c * n_blocks > len(texts):
        raise ValueError(f"{c * n_blocks} distinct texts needed, corpus has "
                         f"{len(texts)}")
    lo, hi = mix["prompt_len"]
    shape = np.random.default_rng(SHAPE_SEED)
    out = []
    for b in range(n_blocks):
        block = texts[b * c:(b + 1) * c]
        block_lens = shape.permutation(_lengths(c, lo, hi))
        for text, length in zip(block, block_lens):
            out.append(Draft(text=text,
                             prompt=prompt_ids(text, int(length), seed,
                                               vocab),
                             max_new=int(mix["max_new"]), due_s=0.0))
    return out

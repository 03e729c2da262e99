"""The traffic generator: deterministic from the seed, every seed the
same work in another order, repeated texts carry the same prompt ids, and
the lengths and tasks the mixes state."""
import collections
import os

import numpy as np
import pytest

from bench import harness, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2**31 + 2**33 + 12345
SECONDS = 51.0
TASKS = {"mmlu", "hellaswag", "arc-challenge", "winogrande"}


@pytest.fixture(scope="module")
def corpus():
    spec = harness.load_cell(ROOT, "pool2-route.mcq")
    data, texts, rows = harness._corpus(spec.mix, spec.config)
    return data, texts, rows


def _mix(name):
    return traffic.load_mix(os.path.join(ROOT, "bench", "traffic",
                                         name + ".json"))


def _open(name, corpus, seed):
    mix = _mix(name)
    return mix, traffic.open_loop(mix, corpus[1], seed, SECONDS,
                                  int(mix["token_vocab"]))


def _key(d):
    return (d.text, d.prompt.tobytes(), d.max_new, d.due_s)


@pytest.mark.parametrize("name", ["mcq", "neardup"])
def test_open_loop_is_deterministic_from_the_seed(name, corpus):
    _, a = _open(name, corpus, BIG_SEED)
    _, b = _open(name, corpus, BIG_SEED)
    _, c = _open(name, corpus, BIG_SEED + 1)
    assert [_key(d) for d in a] == [_key(d) for d in b]
    assert [_key(d) for d in a] != [_key(d) for d in c]


@pytest.mark.parametrize("name", ["mcq", "neardup"])
def test_every_seed_gets_the_same_work(name, corpus):
    mix, a = _open(name, corpus, 7)
    _, b = _open(name, corpus, BIG_SEED)
    assert len(a) == len(b) == round(mix["rate_per_s"] * SECONDS)
    assert (collections.Counter((d.text, len(d.prompt)) for d in a)
            == collections.Counter((d.text, len(d.prompt)) for d in b))
    assert sorted(np.diff([0.0] + sorted(d.due_s for d in a))) == \
        pytest.approx(sorted(np.diff([0.0] + sorted(d.due_s for d in b))))
    for drafts in (a, b):
        due = [d.due_s for d in drafts]
        assert min(due) > 0 and max(due) < SECONDS


def test_lengths_and_tasks_as_the_mix_states(corpus):
    data, texts, rows = corpus
    for name in ("mcq", "neardup"):
        mix, drafts = _open(name, corpus, BIG_SEED)
        lo, hi = mix["prompt_len"]
        lens = np.array([len(d.prompt) for d in drafts])
        assert lens.min() >= lo and lens.max() <= hi
        # Lengths stratify [lo, hi]: every quarter of the range is used.
        assert len(set(((lens - lo) * 4) // (hi - lo + 1))) == 4
        assert {data.benchmark[rows[d.text]] for d in drafts} <= TASKS
        assert all(d.max_new == mix["max_new"] for d in drafts)
        assert all(int(d.prompt.max()) < mix["token_vocab"]
                   and int(d.prompt.min()) >= 0 for d in drafts)


def test_repeated_texts_carry_the_same_prompt_ids(corpus):
    mix, drafts = _open("neardup", corpus, BIG_SEED)
    by_text = collections.defaultdict(set)
    for d in drafts:
        by_text[d.text].add(d.prompt.tobytes())
    assert all(len(v) == 1 for v in by_text.values())
    counts = collections.Counter(d.text for d in drafts)
    repeats = sum(c - 1 for c in counts.values())
    # 70% of arrivals repeat a text of the 32-text hot set (the first
    # arrival of a hot text is not a repeat of an earlier one).
    n_hot = round(mix["repeat_frac"] * len(drafts))
    assert repeats >= n_hot - mix["hot_set"]
    assert len([t for t, c in counts.items() if c > 1]) <= mix["hot_set"]


def test_closed_loop_blocks_hold_the_same_work_for_every_seed(corpus):
    mix = _mix("mcq-batch")
    a = traffic.closed_loop(mix, corpus[1], 3, 2, mix["token_vocab"])
    b = traffic.closed_loop(mix, corpus[1], BIG_SEED, 2, mix["token_vocab"])
    c = mix["clients"]
    assert len(a) == len(b) == 2 * c
    for blk in range(2):
        sa, sb = a[blk * c:(blk + 1) * c], b[blk * c:(blk + 1) * c]
        assert (sorted((d.text, len(d.prompt)) for d in sa)
                == sorted((d.text, len(d.prompt)) for d in sb))
    assert [d.text for d in a] != [d.text for d in b]


def test_prompt_ids_depend_on_text_and_seed():
    a = traffic.prompt_ids("q", 50, BIG_SEED, 1000)
    assert np.array_equal(a, traffic.prompt_ids("q", 50, BIG_SEED, 1000))
    assert not np.array_equal(a, traffic.prompt_ids("r", 50, BIG_SEED, 1000))
    assert not np.array_equal(
        a, traffic.prompt_ids("q", 50, BIG_SEED + 2**32, 1000))
    with pytest.raises(ValueError):
        traffic.prompt_ids("q", 5, -1, 10)

"""The compiled decode loop: ``greedy_generate``'s tokens equal a step-by-step
eager reference at the exact cache length, for every mixer family of the
smoke members, and one compiled program serves every prompt length of a
cache bucket."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import profile_slot
from repro.configs import get_smoke_config
from repro.models import lm as lm_mod
from repro.obs import LayerProfiler
from repro.serving.engine import pad_prompts, prompt_pad_mask

VOCAB = 64
MAX_NEW = 4

# One smoke member per mixer family the decode loop runs.
FAMILIES = {
    "dense-attention": "qwen3-0.6b",
    "sliding-window": "gemma3-27b",
    "moe": "granite-moe-1b-a400m",
    "ssm": "jamba-1.5-large-398b",
    "xlstm": "xlstm-1.3b",
    "media-cross-attention": "llama-3.2-vision-90b",
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    cfg = get_smoke_config(FAMILIES[request.param])
    params = lm_mod.init_lm(jax.random.key(0), cfg)
    return cfg, params


def _media(cfg, b):
    if not cfg.n_frontend_tokens:
        return None
    return jax.random.normal(jax.random.key(3),
                             (b, cfg.n_frontend_tokens, cfg.frontend_dim))


def _reference(cfg, params, prompt, max_new, media=None, attn_mask=None):
    """Eager prefill, then one eager decode step at a time, with caches of
    exactly ``S + max_new`` slots."""
    b, s = prompt.shape
    caches = lm_mod.init_caches(cfg, b, s + max_new)
    logits, caches = lm_mod.apply_lm_prefill(cfg, params, prompt, caches,
                                             media, attn_mask=attn_mask)
    out = []
    for i in range(max_new):
        tok = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)[:, None]
        out.append(np.asarray(tok))
        if i + 1 < max_new:
            logits, caches = lm_mod.apply_lm_decode(cfg, params, tok, caches,
                                                    jnp.int32(s + i))
    return np.concatenate(out, axis=1)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).astype(np.int32) for n in lengths]


def test_bucket_is_the_next_multiple_of_256():
    assert [lm_mod.cache_len(s, 8) for s in (120, 248, 249, 504, 760, 899)] \
        == [256, 256, 512, 512, 768, 1024]


def test_left_padded_batch_matches_the_eager_reference(family):
    """Rows of 5, 17 and 11 tokens: 21 slots needed, 256 allocated; the
    window-8 layers wrap their ring inside the prompt."""
    cfg, params = family
    prompts = _prompts((5, 17, 11))
    toks, mask = pad_prompts(prompts), prompt_pad_mask(prompts)
    media = _media(cfg, len(prompts))
    assert lm_mod.cache_len(toks.shape[1], MAX_NEW) > toks.shape[1] + MAX_NEW
    got = np.asarray(lm_mod.greedy_generate(cfg, params, toks, MAX_NEW,
                                            media=media, attn_mask=mask))
    want = _reference(cfg, params, toks, MAX_NEW, media=media,
                      attn_mask=mask)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("s", [252, 253], ids=["fills-bucket", "next-bucket"])
def test_bucket_edges_match_the_eager_reference(s):
    """``S + max_new`` exactly 256 (no spare slot), and one past it (a
    512-slot bucket, 255 slots spare)."""
    cfg = get_smoke_config("qwen3-0.6b")
    params = lm_mod.init_lm(jax.random.key(0), cfg)
    prompt = jnp.asarray(np.stack(_prompts((s, s), seed=s)))
    got = np.asarray(lm_mod.greedy_generate(cfg, params, prompt, MAX_NEW))
    np.testing.assert_array_equal(got, _reference(cfg, params, prompt,
                                                  MAX_NEW))


def test_one_compile_per_bucket():
    """Two calls at one batch size whose prompt lengths share a bucket
    compile the decode loop once; a call in the next bucket compiles it
    once more."""
    # A config of its own, so no other test has compiled these keys.
    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"),
                              name="decode-loop-compile-count")
    params = lm_mod.init_lm(jax.random.key(0), cfg)
    prof = LayerProfiler()
    profile_slot.install(prof)
    try:
        for s in (20, 90, 300):
            prompt = jnp.asarray(np.stack(_prompts((s, s, s), seed=s)))
            lm_mod.greedy_generate(cfg, params, prompt, MAX_NEW)
    finally:
        profile_slot.install(None)
    decode = [args for name, *_, args in prof.spans
              if name == "repro.lm.decode"]
    assert [a["cache_len"] for a in decode] == [256, 256, 512]
    assert [a.get("compiles", 0) for a in decode] == [1, 0, 1]
    assert all(a["n"] == 3 and a["steps"] == MAX_NEW - 1 for a in decode)
    # The wait for the device inside each decode span compiles nothing.
    steps = [args for name, *_, args in prof.spans
             if name == "repro.lm.decode_step"]
    assert len(steps) == 3 and not any("compiles" in a for a in steps)

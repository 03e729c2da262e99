"""Composable decoder LM over periodic layer plans.

The stack executes an :class:`ArchConfig`'s layer plan:

    [pattern block_0 ... block_{P-1}] x n_repeats  +  remainder blocks

The repeated pattern runs under ``jax.lax.scan`` with parameters stacked on a
leading (n_repeats) axis — one HLO body per *pattern*, not per layer, which
keeps compile time bounded for the 100-layer pool members. Heterogeneous
blocks inside a pattern (jamba's mamba/attn/moe interleave, gemma3's
local:global, llama-vision's self:cross) are unrolled *within* the scan body.

Three entry points:
  * train:   full causal sequence -> token loss (+ MoE aux)
  * prefill: full sequence -> last-token logits + decode caches
  * decode:  one token + caches -> logits + updated caches
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.profile_slot import active
from repro.configs.base import (
    ATTN, MAMBA, MLP, MLSTM, MOE, NONE, SLSTM, XATTN, ArchConfig, LayerSpec,
)
from repro.models import attention as attn_mod
from repro.models import runtime_flags
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (
    apply_mlp, apply_rmsnorm, embed_tokens, init_embedding, init_mlp,
    init_rmsnorm, lm_logits,
)
from repro.models.sharding_ctx import shard

LOSS_SEQ_CHUNK = 512
# Decode caches are allocated at the next multiple of this many slots
# above prompt + new tokens (see greedy_generate).
CACHE_BUCKET = 256


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ArchConfig, spec: LayerSpec, dtype=jnp.float32) -> Dict:
    k_mix, k_ffn = jax.random.split(key)
    p: Dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    if spec.mixer in (ATTN, XATTN):
        p["mixer"] = attn_mod.init_attention(k_mix, cfg, spec, dtype)
    elif spec.mixer == MAMBA:
        p["mixer"] = ssm_mod.init_mamba(k_mix, cfg, dtype)
    elif spec.mixer == MLSTM:
        p["mixer"] = xlstm_mod.init_mlstm(k_mix, cfg, dtype)
    elif spec.mixer == SLSTM:
        p["mixer"] = xlstm_mod.init_slstm(k_mix, cfg, dtype)
    if spec.ffn != NONE:
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        if spec.ffn == MLP:
            p["ffn"] = init_mlp(k_ffn, cfg.d_model, cfg.d_ff, dtype)
        else:
            p["ffn"] = moe_mod.init_moe(k_ffn, cfg, dtype)
    return p


def _apply_ffn_train(cfg, spec, p, x, mask=None):
    """``mask`` (B, S) bool marks real tokens of a left-padded batch; MoE
    excludes pads from capacity accounting and the aux loss."""
    if spec.ffn == NONE:
        return x, jnp.float32(0.0)
    h = apply_rmsnorm(p["norm2"], x, cfg.norm_eps)
    if spec.ffn == MLP:
        return x + apply_mlp(p["ffn"], h), jnp.float32(0.0)
    y, aux = moe_mod.apply_moe_train(cfg, p["ffn"], h, mask=mask)
    return x + y, aux


def _apply_ffn_decode(cfg, spec, p, x):
    if spec.ffn == NONE:
        return x
    h = apply_rmsnorm(p["norm2"], x, cfg.norm_eps)
    if spec.ffn == MLP:
        return x + apply_mlp(p["ffn"], h)
    return x + moe_mod.apply_moe_decode(cfg, p["ffn"], h)


def apply_block_train(cfg, spec, p, x, positions, media, mask=None):
    """``mask`` (B, S) bool marks real tokens of a left-padded batch; every
    mixer family applies its masked-compute variant (pad keys masked /
    identity recurrence updates / pad-excluded MoE capacity)."""
    h = apply_rmsnorm(p["norm1"], x, cfg.norm_eps)
    if spec.mixer == ATTN:
        y = attn_mod.self_attention_full_seq(cfg, spec, p["mixer"], h, positions,
                                             kv_valid=mask)
    elif spec.mixer == XATTN:
        y = attn_mod.cross_attention_full_seq(cfg, p["mixer"], h, media)
    elif spec.mixer == MAMBA:
        y = ssm_mod.apply_mamba_train(cfg, p["mixer"], h, mask=mask)
    elif spec.mixer == MLSTM:
        y = xlstm_mod.apply_mlstm_train(cfg, p["mixer"], h, mask=mask)
    elif spec.mixer == SLSTM:
        y = xlstm_mod.apply_slstm_train(cfg, p["mixer"], h, mask=mask)
    else:  # pragma: no cover
        raise ValueError(spec.mixer)
    x = x + y
    return _apply_ffn_train(cfg, spec, p, x, mask=mask)


def init_block_cache(cfg, spec, batch: int, max_len: int, dtype=jnp.float32):
    if spec.mixer in (ATTN, XATTN):
        return attn_mod.init_kv_cache(cfg, spec, batch, max_len, dtype)
    if spec.mixer == MAMBA:
        return ssm_mod.init_mamba_cache(cfg, batch, dtype)
    if spec.mixer == MLSTM:
        return xlstm_mod.init_mlstm_cache(cfg, batch, dtype)
    if spec.mixer == SLSTM:
        return xlstm_mod.init_slstm_cache(cfg, batch, dtype)
    raise ValueError(spec.mixer)  # pragma: no cover


def apply_block_prefill(cfg, spec, p, x, positions, media, cache,
                        attn_mask=None):
    """Full-sequence pass that also fills this block's decode cache.

    ``attn_mask`` (B, S) bool marks real tokens of a left-padded batch.
    Every mixer family is batch-composition invariant under it: attention
    masks pad keys (and records per-row validity in the decode cache);
    SSM/xLSTM recurrences treat pad steps as identity updates so the
    carried state — which *is* the decode cache — crosses pads unchanged;
    MoE excludes pads from capacity accounting. Pinned by the cross-mixer
    harness in tests/test_masked_prefill.py.
    """
    h = apply_rmsnorm(p["norm1"], x, cfg.norm_eps)
    if spec.mixer == ATTN:
        y = attn_mod.self_attention_full_seq(cfg, spec, p["mixer"], h, positions,
                                             kv_valid=attn_mask)
        cache = attn_mod.prefill_self_cache(cfg, spec, p["mixer"], h, positions,
                                            cache, kv_valid=attn_mask)
    elif spec.mixer == XATTN:
        y = attn_mod.cross_attention_full_seq(cfg, p["mixer"], h, media)
        cache = attn_mod.prefill_cross_cache(cfg, p["mixer"], media, cache)
    elif spec.mixer == MAMBA:
        y, state = ssm_mod.apply_mamba_train(cfg, p["mixer"], h,
                                             return_state=True, mask=attn_mask)
        cache = {**cache, "h": state["h"],
                 "conv": state["conv"].astype(cache["conv"].dtype)}
    elif spec.mixer == MLSTM:
        y, state = xlstm_mod.apply_mlstm_train(cfg, p["mixer"], h,
                                               return_state=True, mask=attn_mask)
        cache = {**cache, "C": state["C"], "n": state["n"], "m": state["m"],
                 "conv": state["conv"].astype(cache["conv"].dtype)}
    elif spec.mixer == SLSTM:
        y, state = xlstm_mod.apply_slstm_train(cfg, p["mixer"], h,
                                               return_state=True, mask=attn_mask)
        cache = {**cache, **state}
    else:  # pragma: no cover
        raise ValueError(spec.mixer)
    x = x + y
    # Prefill uses the train-path FFN: chunked capacity dispatch for MoE
    # (decode-path dispatch over B*S tokens at once would blow up memory).
    x, _ = _apply_ffn_train(cfg, spec, p, x, mask=attn_mask)
    return x, cache


def apply_block_decode(cfg, spec, p, x, pos, cache):
    h = apply_rmsnorm(p["norm1"], x, cfg.norm_eps)
    if spec.mixer == ATTN:
        y, cache = attn_mod.self_attention_decode(cfg, spec, p["mixer"], h, cache, pos)
    elif spec.mixer == XATTN:
        y, cache = attn_mod.cross_attention_decode(cfg, p["mixer"], h, cache)
    elif spec.mixer == MAMBA:
        y, cache = ssm_mod.apply_mamba_decode(cfg, p["mixer"], h, cache)
    elif spec.mixer == MLSTM:
        y, cache = xlstm_mod.apply_mlstm_decode(cfg, p["mixer"], h, cache)
    elif spec.mixer == SLSTM:
        y, cache = xlstm_mod.apply_slstm_decode(cfg, p["mixer"], h, cache)
    else:  # pragma: no cover
        raise ValueError(spec.mixer)
    x = x + y
    x = _apply_ffn_decode(cfg, spec, p, x)
    return x, cache


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    k_emb, k_pat, k_rem = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embedding": init_embedding(k_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    pat = tuple(cfg.pattern)

    def init_repeat(k):
        ks = jax.random.split(k, len(pat))
        return tuple(init_block(ks[i], cfg, pat[i], dtype) for i in range(len(pat)))

    if cfg.n_repeats > 0:
        params["pattern"] = jax.vmap(init_repeat)(
            jax.random.split(k_pat, cfg.n_repeats)
        )
    if cfg.remainder:
        ks = jax.random.split(k_rem, len(cfg.remainder))
        params["remainder"] = tuple(
            init_block(ks[i], cfg, spec, dtype)
            for i, spec in enumerate(cfg.remainder)
        )
    return params


def abstract_params(cfg: ArchConfig, dtype=jnp.float32):
    """ShapeDtypeStruct tree of the full-size parameters (no allocation)."""
    return jax.eval_shape(
        functools.partial(init_lm, cfg=cfg, dtype=dtype), jax.random.key(0)
    )


def _positions(tokens: jax.Array) -> jax.Array:
    b, s = tokens.shape
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))



def _outer_scan(body, x, xs, n: int):
    """lax.scan over stacked layer-pattern params/caches, or a Python loop
    under the roofline probe flag (see runtime_flags)."""
    if not runtime_flags.UNROLL_INNER:
        return jax.lax.scan(body, x, xs)
    ys = []
    for i in range(n):
        x, y = body(x, jax.tree.map(lambda a, i=i: a[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs, 0), *ys)
    else:
        ys = None
    return x, ys


def _backbone_train(cfg, params, x, positions, media, remat: bool = True,
                    mask=None):
    """Run the layer plan over (B,S,D) activations. Returns (x, moe aux)."""
    aux_total = jnp.float32(0.0)
    pat = tuple(cfg.pattern)
    if cfg.n_repeats > 0:
        def body(x, pslice):
            aux = jnp.float32(0.0)
            for i, spec in enumerate(pat):
                x, a = apply_block_train(cfg, spec, pslice[i], x, positions,
                                         media, mask=mask)
                aux = aux + a
            x = shard(x, "batch", "seq", "embed")
            return x, aux

        if remat:
            body = jax.checkpoint(body)
        x, auxes = _outer_scan(body, x, params["pattern"], cfg.n_repeats)
        aux_total = aux_total + auxes.sum()
    for i, spec in enumerate(cfg.remainder):
        x, a = apply_block_train(cfg, spec, params["remainder"][i], x, positions,
                                 media, mask=mask)
        aux_total = aux_total + a
    return apply_rmsnorm(params["final_norm"], x, cfg.norm_eps), aux_total


def apply_lm_train(cfg, params, tokens, media=None, remat=True, attn_mask=None):
    """Full logits (small-vocab / test path). Returns (logits, aux).

    ``attn_mask`` (B, S) bool marks real tokens of a left-padded batch
    (None = all real); masked compute applies in every mixer family.
    """
    x = embed_tokens(params["embedding"], tokens)
    x = shard(x, "batch", "seq", "embed")
    x, aux = _backbone_train(cfg, params, x, _positions(tokens), media, remat,
                             mask=attn_mask)
    return lm_logits(params["embedding"], x), aux


def lm_loss(cfg, params, tokens, labels, media=None, remat=True,
            attn_mask=None):
    """Next-token CE + MoE aux, computed in sequence chunks so the
    (B, S, padded_vocab) logits tensor never fully materializes.

    ``attn_mask`` (B, S) bool marks real tokens of a left-padded batch:
    pad positions are excluded from the CE (numerator *and* denominator)
    and, through the backbone, from MoE capacity/aux accounting.
    """
    x = embed_tokens(params["embedding"], tokens)
    x = shard(x, "batch", "seq", "embed")
    x, aux = _backbone_train(cfg, params, x, _positions(tokens), media, remat,
                             mask=attn_mask)

    b, s, d = x.shape
    head = params["embedding"]["head"]

    @jax.checkpoint
    def chunk_loss(xc, lc, mc=None):
        logits = (xc @ head).astype(jnp.float32)
        pad = logits.shape[-1] - cfg.vocab_size
        if pad > 0:
            logits = logits - jnp.concatenate(
                [jnp.zeros((cfg.vocab_size,)), jnp.full((pad,), 1e30)]
            )
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        tok_loss = lse - gold
        if mc is not None:
            tok_loss = tok_loss * mc
        return jnp.sum(tok_loss)

    chunk = min(LOSS_SEQ_CHUNK, s)
    if s % chunk == 0 and s > chunk:
        n = s // chunk
        args = (x.reshape(b, n, chunk, d).swapaxes(0, 1),
                labels.reshape(b, n, chunk).swapaxes(0, 1))
        if attn_mask is not None:
            args += (attn_mask.reshape(b, n, chunk).swapaxes(0, 1),)
        if runtime_flags.UNROLL_INNER:
            total = sum(chunk_loss(*(a[i] for a in args)) for i in range(n))
        else:
            totals = jax.lax.map(lambda aa: chunk_loss(*aa), args)
            total = totals.sum()
    else:
        total = chunk_loss(x, labels, attn_mask)
    denom = (b * s) if attn_mask is None else jnp.maximum(attn_mask.sum(), 1)
    loss = total / denom
    return loss + cfg.router_aux_coef * aux


def init_caches(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.float32):
    """Decode caches matching the params tree layout (pattern stacked)."""
    pat = tuple(cfg.pattern)
    caches: Dict[str, Any] = {}

    def one_repeat(_):
        return tuple(
            init_block_cache(cfg, spec, batch, max_len, dtype) for spec in pat
        )

    if cfg.n_repeats > 0:
        caches["pattern"] = jax.vmap(one_repeat)(jnp.arange(cfg.n_repeats))
    if cfg.remainder:
        caches["remainder"] = tuple(
            init_block_cache(cfg, spec, batch, max_len, dtype)
            for spec in cfg.remainder
        )
    return caches


def abstract_caches(cfg, batch, max_len, dtype=jnp.float32):
    return jax.eval_shape(
        functools.partial(init_caches, cfg, batch, max_len, dtype)
    )


def apply_lm_prefill(cfg, params, tokens, caches, media=None, attn_mask=None):
    """Prefill: full forward + cache build. Returns (last_logits, caches).

    ``attn_mask`` (B, S) bool marks real tokens of a left-padded batch
    (None = all real); see :func:`apply_block_prefill`.
    """
    x = embed_tokens(params["embedding"], tokens)
    x = shard(x, "batch", "seq", "embed")
    positions = _positions(tokens)
    pat = tuple(cfg.pattern)
    new_caches: Dict[str, Any] = {}
    if cfg.n_repeats > 0:
        def apply_repeat(x, pslice, cslice):
            new = []
            for j, spec in enumerate(pat):
                x, c = apply_block_prefill(
                    cfg, spec, pslice[j], x, positions, media, cslice[j],
                    attn_mask=attn_mask,
                )
                new.append(c)
            x = shard(x, "batch", "seq", "embed")
            return x, tuple(new)

        if runtime_flags.UNROLL_INNER:
            def body(x, inputs):
                pslice, cslice = inputs
                return apply_repeat(x, pslice, cslice)

            x, new_caches["pattern"] = _outer_scan(
                body, x, (params["pattern"], caches["pattern"]), cfg.n_repeats
            )
        else:
            # Carry-threaded caches: in-place update, no xs/ys double buffer
            # (same rationale as apply_lm_decode).
            def body_carry(carry, inputs):
                x, cache_stack = carry
                i, pslice = inputs
                cslice = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                           keepdims=False),
                    cache_stack,
                )
                x, new = apply_repeat(x, pslice, cslice)
                cache_stack = jax.tree.map(
                    lambda st, nc: jax.lax.dynamic_update_index_in_dim(
                        st, nc.astype(st.dtype), i, 0),
                    cache_stack, new,
                )
                return (x, cache_stack), None

            (x, new_caches["pattern"]), _ = jax.lax.scan(
                body_carry, (x, caches["pattern"]),
                (jnp.arange(cfg.n_repeats), params["pattern"]),
            )
    if cfg.remainder:
        new_rem = []
        for i, spec in enumerate(cfg.remainder):
            x, c = apply_block_prefill(
                cfg, spec, params["remainder"][i], x, positions, media,
                caches["remainder"][i], attn_mask=attn_mask,
            )
            new_rem.append(c)
        new_caches["remainder"] = tuple(new_rem)
    x_last = apply_rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    return lm_logits(params["embedding"], x_last), new_caches


def apply_lm_decode(cfg, params, token, caches, pos):
    """One decode step. token (B,1) int32; pos scalar int32 (next position).

    The stacked caches thread through the scan CARRY and are updated in
    place with ``dynamic_update_index_in_dim``. The earlier xs/ys form kept
    TWO copies of the full KV cache live (scan xs and ys cannot alias):
    decode temps were ~2.6x the cache size (EXPERIMENTS.md §Perf iteration
    "decode-carry-cache").
    """
    x = embed_tokens(params["embedding"], token)
    pat = tuple(cfg.pattern)
    new_caches: Dict[str, Any] = {}
    if cfg.n_repeats > 0:
        def apply_repeat(x, pslice, cslice):
            new = []
            for j, spec in enumerate(pat):
                x, c = apply_block_decode(cfg, spec, pslice[j], x, pos, cslice[j])
                new.append(c)
            return x, tuple(new)

        if runtime_flags.UNROLL_INNER:
            def body(x, inputs):
                pslice, cslice = inputs
                return apply_repeat(x, pslice, cslice)

            x, new_caches["pattern"] = _outer_scan(
                body, x, (params["pattern"], caches["pattern"]), cfg.n_repeats
            )
        else:
            def body_carry(carry, inputs):
                x, cache_stack = carry
                i, pslice = inputs
                cslice = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                           keepdims=False),
                    cache_stack,
                )
                x, new = apply_repeat(x, pslice, cslice)
                cache_stack = jax.tree.map(
                    lambda st, nc: jax.lax.dynamic_update_index_in_dim(
                        st, nc.astype(st.dtype), i, 0),
                    cache_stack, new,
                )
                return (x, cache_stack), None

            (x, new_caches["pattern"]), _ = jax.lax.scan(
                body_carry, (x, caches["pattern"]),
                (jnp.arange(cfg.n_repeats), params["pattern"]),
            )
    if cfg.remainder:
        new_rem = []
        for i, spec in enumerate(cfg.remainder):
            x, c = apply_block_decode(
                cfg, spec, params["remainder"][i], x, pos, caches["remainder"][i]
            )
            new_rem.append(c)
        new_caches["remainder"] = tuple(new_rem)
    x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_logits(params["embedding"], x), new_caches


def greedy_generate(cfg, params, prompt, max_new: int, media=None,
                    dtype=jnp.float32, attn_mask=None):
    """Greedy decoding: eager prefill, then :func:`decode_loop`.

    ``attn_mask`` (B, S) bool marks real prompt tokens of a left-padded
    batch so every pool member's output — attention, SSM, xLSTM, and MoE
    alike — is invariant to micro-batch composition (see serving engine
    ``pad_prompts`` and tests/test_masked_prefill.py).

    The decode caches hold :func:`cache_len` slots, the bucket of
    ``S + max_new``: slots past the last written position stay empty
    (``slot_pos`` -1) and are masked, so the tokens are those of a cache
    of exactly ``S + max_new``, and calls whose prompt lengths share a
    bucket reuse one compiled decode loop.

    With the layer profiler installed (:mod:`repro.common.profile_slot`)
    the prefill runs as span ``repro.lm.prefill`` (``n``, ``length``),
    closed once the first token is ready, and the decode loop as
    ``repro.lm.decode`` (``n``, ``steps``, ``cache_len``), closed once the
    last token is ready; a compile of the loop is charged to the latter.
    Inside it ``repro.lm.decode_step`` (``steps``) is the wait for the
    loop's steps on the device, after its dispatch. The tokens are the
    same either way.
    """
    prof = active()
    b, s = prompt.shape
    length = cache_len(s, max_new)
    with _span(prof, "repro.lm.prefill", n=b, length=s):
        caches = init_caches(cfg, b, length, dtype)
        logits, caches = apply_lm_prefill(cfg, params, prompt, caches, media,
                                          attn_mask=attn_mask)
        tok = _greedy_token(cfg, logits)
        if prof is not None:
            jax.block_until_ready(tok)
    with _span(prof, "repro.lm.decode", n=b, steps=max_new - 1,
               cache_len=length):
        rest, _ = decode_loop(params, tok, caches, s, cfg=cfg,
                              max_new=max_new)
        if prof is not None:
            with prof.span("repro.lm.decode_step", steps=max_new - 1):
                jax.block_until_ready(rest)
    return jnp.concatenate([tok, rest], axis=1)


def cache_len(s: int, max_new: int) -> int:
    """Decode-cache slots for an ``s``-token prompt and ``max_new`` new
    tokens: ``s + max_new`` rounded up to a multiple of
    :data:`CACHE_BUCKET`."""
    return -(-(s + max_new) // CACHE_BUCKET) * CACHE_BUCKET


@functools.partial(jax.jit, static_argnames=("cfg", "max_new"),
                   donate_argnames=("caches",))
def decode_loop(params, first_tok, caches, s, *, cfg: ArchConfig,
                max_new: int):
    """The ``max_new - 1`` greedy steps after ``first_tok`` (B, 1), as one
    program per (config, ``max_new``, batch, cache length).

    ``s``, the prompt length and so the first decode position, is traced,
    so prompts of any length in one cache bucket share the program.
    ``params`` is an argument, never a constant of the program. The caches
    are donated and come back updated in place (returning them is what
    lets the donation alias them), so one copy is live. Returns
    ``(tokens (B, max_new - 1), caches)``.
    """
    def step(carry, i):
        tok, caches = carry
        logits, caches = apply_lm_decode(cfg, params, tok, caches, s + i)
        tok = _greedy_token(cfg, logits)
        return (tok, caches), tok[:, 0]

    (_, caches), toks = jax.lax.scan(
        step, (first_tok, caches), jnp.arange(max_new - 1, dtype=jnp.int32))
    return toks.T, caches


def _greedy_token(cfg, logits):
    return jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)[:, None]


def _span(prof, name, **args):
    if prof is None:
        return contextlib.nullcontext()
    return prof.span(name, **args)

"""Routed serving engine: the paper's router fronting the architecture pool.

Flow per score batch:
    text -> featurizer -> dual predictors (quality, cost) -> reward argmax
         -> dispatch to the chosen pool member's generate loop.

The pool members are the assigned architectures (reduced configs for the
CPU tests, published widths on the chip). Each member's $ cost rate derives
from its *active* parameter count — 2*N_active FLOPs/token at a fixed
$/FLOP — so the router's cost axis is grounded in real model economics
rather than API price tables.

:class:`RoutedEngine` is the *stateless* scoring/dispatch core: it owns no
queue, no clock, and no budget — the streaming scheduler
(:mod:`repro.serving.scheduler`) drives it. The router's scoring hot path
runs through the fused Pallas kernel (``repro.kernels.ops.router_xattn_pool``)
on TPU when the quality predictor is the attention variant, with the
pool-side K~/V~ projections computed once per pool and reused across every
score batch; on the CPU it runs the jnp reference path (identical math,
see kernels/ref.py) unless ``use_pallas`` asks for the kernel in
interpret mode.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.profile_slot import active
from repro.core.predictors import ENSEMBLE_KINDS, PREDICTORS
from repro.core.rewards import REWARDS
from repro.core.router import PredictiveRouter
from repro.data.featurizer import embed_texts
from repro.kernels import ops as kops
from repro.models import lm as lm_mod

# $ per 1e12 FLOPs — anchors active-param FLOPs to an API-like price axis.
DOLLARS_PER_TFLOP = 2.2e-4

# Nominal generation length the per-request $ rate is quoted at. The
# router's cost axis trains on this flat rate (a stable, request-agnostic
# per-member price that keeps the ladder ordering deterministic), while
# the actual ledger charge is per *delivered* token (see
# ``generate_member``): ``cost_rate / REF_TOKENS_OUT`` $ per token.
REF_TOKENS_OUT = 256


def arch_cost_per_token(cfg) -> float:
    """$ per token processed: 2 * N_active FLOPs/token * $/FLOP."""
    return 2.0 * cfg.active_param_count() / 1e12 * DOLLARS_PER_TFLOP


def arch_cost_rate(cfg, tokens_out: int = REF_TOKENS_OUT) -> float:
    """Nominal $ per request at the reference generation length."""
    return arch_cost_per_token(cfg) * tokens_out


@dataclasses.dataclass
class PoolMember:
    name: str
    cfg: object
    params: Dict
    quality_profile: Callable[[np.ndarray], np.ndarray]  # emb -> quality sim
    cost_rate: float

    def generate(self, prompts: jax.Array, max_new: int = 8, attn_mask=None):
        return lm_mod.greedy_generate(self.cfg, self.params, prompts, max_new,
                                      attn_mask=attn_mask)


def pad_prompts(prompts: Sequence[np.ndarray], pad_id: int = 0) -> jax.Array:
    """Left-pad variable-length token rows into one (B, S_max) int32 batch.

    Left padding keeps the *last* prompt position real, which is what the
    greedy prefill conditions the first generated token on. Pass the
    matching :func:`prompt_pad_mask` into generate so every mixer family
    ignores pad positions — attention masks pad keys, SSM/xLSTM scans
    treat pads as identity updates, MoE excludes pads from capacity
    accounting — making each request's output invariant to its micro-batch
    neighbors (pinned by tests/test_masked_prefill.py).
    """
    s_max = max(int(len(p)) for p in prompts)
    out = np.full((len(prompts), s_max), pad_id, np.int32)
    for i, p in enumerate(prompts):
        p = np.asarray(p, np.int32)
        out[i, s_max - len(p):] = p
    return jnp.asarray(out)


def prompt_pad_mask(prompts: Sequence[np.ndarray]) -> jax.Array:
    """(B, S_max) bool, True at real (right-aligned) token positions."""
    s_max = max(int(len(p)) for p in prompts)
    mask = np.zeros((len(prompts), s_max), bool)
    for i, p in enumerate(prompts):
        mask[i, s_max - len(p):] = True
    return jnp.asarray(mask)


@dataclasses.dataclass
class RoutedEngine:
    """Stateless scoring/dispatch core driven by the streaming scheduler.

    Holds only the trained router and the model pool; every method is a pure
    function of its arguments (plus the lazily cached per-pool K~/V~
    projections, invalidated via :meth:`refresh_pool`).
    """

    router: PredictiveRouter
    pool: List[PoolMember]
    lam: float = 1.0
    # Score through the fused Pallas kernel. Always on where the kernel
    # compiles (TPU); on the CPU the jnp reference scores unless asked.
    use_pallas: bool = False
    # Observability hook: called with the new router version after every
    # successful swap (the scheduler wires this to the trace recorder).
    on_swap: Optional[Callable[[int], None]] = dataclasses.field(
        default=None, repr=False)
    _pool_proj: Optional[Tuple[jax.Array, jax.Array]] = dataclasses.field(
        default=None, repr=False)

    def __post_init__(self):
        self.use_pallas = (bool(self.use_pallas)
                           or jax.default_backend() == "tpu")

    # -- scoring ------------------------------------------------------------

    def pool_projections(self) -> Tuple[jax.Array, jax.Array]:
        """Cached pool-side K~/V~ for the fused scoring path (once per pool)."""
        if self._pool_proj is None:
            qp = self.router.quality_params
            self._pool_proj = kops.pool_projections(
                qp["wk"], qp["wv"], jnp.asarray(self.router.model_emb))
        return self._pool_proj

    def refresh_pool(self) -> None:
        """Invalidate cached projections after the pool/router changes."""
        self._pool_proj = None

    def _scores(self, q_emb: np.ndarray):
        kernel = self.use_pallas and self.router.quality_kind == "attn"
        score = self._kernel_scores if kernel else self.router.predict
        prof = active()
        if prof is None:
            return score(q_emb)
        with prof.span("repro.engine.score", n=len(q_emb),
                       path="kernel" if kernel else "jnp"):
            return score(q_emb)

    def _kernel_scores(self, q_emb: np.ndarray):
        qp = self.router.quality_params
        kt, vt = self.pool_projections()
        # Bucket the batch dim to multiples of 64 *outside* the jit
        # boundary: scheduler batches vary per round, and jit keys on
        # the raw shape — without bucketing every distinct batch size
        # would retrace and recompile the kernel.
        b = q_emb.shape[0]
        b_pad = -(-b // 64) * 64
        q = jnp.asarray(np.pad(np.asarray(q_emb, np.float32),
                               ((0, b_pad - b), (0, 0))))
        s_hat = np.asarray(kops.router_xattn_pool(
            q, qp["wq"], kt, vt, qp["wo"], qp["bo"]))[:b]
        cp = self.router.cost_params
        c_hat = self.router.denormalize_cost(
            PREDICTORS[self.router.cost_kind].apply(
                cp, jnp.asarray(q_emb), jnp.asarray(self.router.model_emb)))
        return s_hat, c_hat

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        """Query embeddings (B, dq) — exposed so the online adapter can
        reuse the scoring pass's embeddings for replay/drift without a
        second featurizer pass."""
        prof = active()
        if prof is None:
            return embed_texts(texts)
        with prof.span("repro.engine.embed", n=len(texts)):
            return embed_texts(texts)

    def score_emb(self, q_emb: np.ndarray):
        """(s_hat, c_hat), both (B, K), from precomputed embeddings."""
        return self._scores(q_emb)

    def score_emb_uncertainty(self, q_emb: np.ndarray):
        """(s_mean, s_std, c_hat), each (B, K) — the cascade scoring path.

        Ensemble quality kinds report per-head disagreement as epistemic
        std; everything else degrades to zero std (the cascade policy then
        runs on means alone). This path stays on the jnp reference scorer:
        the fused Pallas kernel computes a single output head, and the
        per-head spread is exactly what it would fuse away.
        """
        prof = active()
        if prof is None:
            return self.router.predict_with_uncertainty(q_emb)
        path = ("ensemble" if self.router.quality_kind in ENSEMBLE_KINDS
                else "jnp")
        with prof.span("repro.engine.score", n=len(q_emb), path=path):
            return self.router.predict_with_uncertainty(q_emb)

    def score_texts(self, texts: Sequence[str]):
        """(s_hat, c_hat), both (B, K) — one fused pass over the batch."""
        return self._scores(embed_texts(texts))

    # -- online adaptation ---------------------------------------------------

    def swap_router(self, new_router) -> None:
        """Atomically publish a new router version.

        The swap is a single reference assignment of a fully-built router
        (the updater constructs the whole param tree before calling this),
        so a concurrent scorer sees either the old or the new router —
        never a partially-written tree. Stale publishes (version <= live
        version with the same object identity contract) are rejected so a
        slow updater can't roll back a newer router.
        """
        if new_router is self.router:
            raise ValueError("swap_router needs a new router object "
                             "(routers are immutable; use with_updates)")
        if new_router.version <= self.router.version:
            raise ValueError(
                f"stale router publish: v{new_router.version} <= "
                f"live v{self.router.version}")
        self.router = new_router
        self.refresh_pool()
        if self.on_swap is not None:
            self.on_swap(new_router.version)

    def choose(self, s_hat: np.ndarray, c_hat: np.ndarray,
               lam: Optional[float] = None) -> np.ndarray:
        """Reward argmax over the pool at willingness-to-pay ``lam``."""
        lam = self.lam if lam is None else lam
        r = REWARDS[self.router.reward](s_hat, c_hat, lam)
        return np.argmax(np.asarray(r), axis=-1)

    def route_texts(self, texts: Sequence[str],
                    lam: Optional[float] = None) -> np.ndarray:
        s_hat, c_hat = self.score_texts(texts)
        return self.choose(s_hat, c_hat, lam)

    # -- dispatch -----------------------------------------------------------

    def generate_member(self, member_idx: int, prompts: Sequence[np.ndarray],
                        max_new: int = 8,
                        max_new_per_req: Optional[Sequence[int]] = None,
                        ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Run one generate micro-batch on a pool member.

        ``prompts`` are variable-length token rows; they are left-padded
        into one batch. Returns ``(per-request output tokens, per-request
        $ costs)``. The charge is *delivered work* — prefill (prompt
        tokens) plus the new tokens each request actually receives (capped
        by its own ``max_new_per_req`` entry when given, so chunk-mates
        with different caps pay different $ even though the micro-batch
        generates to the chunk max) — at the member's per-token rate,
        never a flat per-request price.
        """
        prof = active()
        if prof is None:
            return self._generate_member(member_idx, prompts, max_new,
                                         max_new_per_req)
        with prof.span("repro.engine.generate",
                       member=self.pool[member_idx].name, n=len(prompts),
                       length=max(len(p) for p in prompts), max_new=max_new):
            return self._generate_member(member_idx, prompts, max_new,
                                         max_new_per_req)

    def _generate_member(self, member_idx, prompts, max_new, max_new_per_req):
        member = self.pool[member_idx]
        toks = member.generate(pad_prompts(prompts), max_new=max_new,
                               attn_mask=prompt_pad_mask(prompts))
        outs = [np.asarray(toks[i]) for i in range(len(prompts))]
        per_tok = member.cost_rate / REF_TOKENS_OUT
        caps = (max_new_per_req if max_new_per_req is not None
                else [max_new] * len(prompts))
        costs = np.asarray(
            [per_tok * (len(np.asarray(p)) + min(len(o), int(cap)))
             for p, o, cap in zip(prompts, outs, caps)], np.float64)
        return outs, costs

    def serve(self, texts: Sequence[str], prompts: jax.Array,
              max_new: int = 8) -> Dict:
        """One-shot batch serving (no queue): route, then generate.

        Requests routed to the same member are coalesced into one generate
        call. The streaming scheduler supersedes this for sustained traffic;
        it remains the simple synchronous entry point.
        """
        t0 = time.time()
        choices = self.route_texts(texts)
        out_tokens = [None] * len(texts)
        total_cost = 0.0
        prompts = np.asarray(prompts)
        for mi in range(len(self.pool)):
            idx = np.flatnonzero(choices == mi)
            if len(idx) == 0:
                continue
            outs, cost = self.generate_member(
                mi, [prompts[i] for i in idx], max_new=max_new)
            for j, ii in enumerate(idx):
                out_tokens[ii] = outs[j]
            total_cost += float(np.sum(cost))
        return {
            "choices": choices,
            "outputs": out_tokens,
            "total_cost": total_cost,
            "latency_s": time.time() - t0,
            "per_member_counts": np.bincount(choices, minlength=len(self.pool)),
        }

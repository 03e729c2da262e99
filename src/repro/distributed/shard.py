"""Pool-member sharding: each worker owns a shard of the LM pool.

The paper's setting (and RouterBench's) is a pool of heterogeneous LLMs
too large to co-host: the router is tiny, the members are not. This
module splits pool ownership across the worker fleet:

  * :func:`owner_of` — deterministic member -> worker placement
    (round-robin by index, stable under worker count);
  * :func:`place_pool` — in one process, put each member's parameters
    on its owner's device (the in-process plane's chip placement);
  * :func:`shard_pool` — on a worker process, lay out the *owned*
    members' parameters with the repo's per-config mesh sharding specs
    (:func:`repro.launch.sharding.param_shardings` over a
    :func:`repro.launch.mesh.make_debug_mesh` by default — the same
    spec tables production meshes use), and evict the parameters of
    members this worker does not own (scoring never reads them; only
    ``PoolMember.generate`` does);
  * :class:`PoolDispatcher` — the scheduler-side indirection: a generate
    micro-batch for a member this worker owns runs locally, any other
    member's batch becomes a ``GENERATE`` message to the owning worker.

The dispatcher preserves ``RoutedEngine.generate_member``'s exact
signature and return contract (per-request output token rows + $ costs),
so the scheduler's delivered-work pricing and telemetry are oblivious to
where the member actually ran.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from repro.distributed import messages as M
from repro.distributed.messages import Message


def owner_of(member_idx: int, n_workers: int) -> int:
    """Which worker owns pool member ``member_idx`` (round-robin)."""
    if n_workers <= 0:
        raise ValueError("n_workers must be positive")
    return int(member_idx) % int(n_workers)


def owned_members(wid: int, n_members: int, n_workers: int) -> List[int]:
    return [mi for mi in range(n_members)
            if owner_of(mi, n_workers) == int(wid)]


def place_pool(pool, n_workers: int, devices=None) -> list:
    """Put each member's parameters on its owning worker's device.

    In one process, worker ``w`` runs on ``devices[w % len(devices)]``
    (default: every local device), so on a host with a chip per worker
    each member generates on its owner's chip; with one device everything
    stays there. Returns the device of each member, in pool order.
    """
    import jax

    devices = list(jax.local_devices() if devices is None else devices)
    placed = []
    for mi, member in enumerate(pool):
        dev = devices[owner_of(mi, n_workers) % len(devices)]
        if member.params is not None:
            member.params = jax.device_put(member.params, dev)
        placed.append(dev)
    return placed


def shard_pool(pool, wid: int, n_workers: int, *, mesh=None,
               evict: bool = True) -> List[int]:
    """Apply mesh sharding to this worker's owned members; evict the rest.

    Returns the owned member indices. ``mesh=None`` uses the single-host
    debug mesh — the sharding *specs* are identical to what a production
    mesh would get, only the device set differs. With ``evict=True`` the
    non-owned members' parameters are dropped (the memory win that makes
    this sharding real); their generates must go through a
    :class:`PoolDispatcher`.
    """
    import jax

    from repro.launch.mesh import make_debug_mesh
    from repro.launch.sharding import param_shardings

    if mesh is None:
        mesh = make_debug_mesh(1, 1)
    owned = []
    for mi, member in enumerate(pool):
        if owner_of(mi, n_workers) == int(wid):
            shardings = param_shardings(member.cfg, mesh, member.params)
            member.params = jax.device_put(member.params, shardings)
            owned.append(mi)
        elif evict:
            member.params = None
    return owned


class PoolDispatcher:
    """Routes generate micro-batches to the member's owning worker.

    Installed as the scheduler's ``dispatcher``: the scheduler calls
    :meth:`generate_member` exactly where it would call the engine's, and
    the dispatcher either runs the batch on the local engine (owned
    member) or ships it as one ``GENERATE`` request to the owner over the
    transport. Remote costs come back as the owner priced them — the
    member's per-token rate is placement-independent, so the budget
    ledger sees identical $ either way.
    """

    def __init__(self, wid: int, n_workers: int, engine, transport):
        self.wid = int(wid)
        self.n_workers = int(n_workers)
        self.engine = engine
        self.transport = transport
        self.stats = {"local": 0, "remote": 0}
        # Trace context for the NEXT generate (set by the scheduler per
        # micro-batch): stamped onto the GENERATE frame so the owner's
        # server span joins the requesting request's causal chain.
        self.trace_key = None
        self.parent_span = None
        # After each call: the remote GENERATE's rpc link id (the request
        # seq, echoed as the reply's reply_to) — None for a local run. The
        # scheduler attaches it to the leg/generate spans as the `rpc` arg.
        self.last_rpc = None

    def owns(self, member_idx: int) -> bool:
        return owner_of(member_idx, self.n_workers) == self.wid

    def generate_member(self, member_idx: int, prompts,
                        max_new: int = 8,
                        max_new_per_req: Optional[List[int]] = None):
        if self.owns(member_idx):
            self.stats["local"] += 1
            self.last_rpc = None
            return self.engine.generate_member(
                member_idx, prompts, max_new=max_new,
                max_new_per_req=max_new_per_req)
        self.stats["remote"] += 1
        owner = owner_of(member_idx, self.n_workers)
        rep = self.transport.request(Message(
            kind=M.GENERATE, dst=owner,
            trace_key=self.trace_key, parent_span=self.parent_span,
            payload={"member": int(member_idx),
                     "prompts": [np.asarray(p) for p in prompts],
                     "max_new": int(max_new),
                     "max_new_per_req": (None if max_new_per_req is None
                                         else [int(m)
                                               for m in max_new_per_req])}))
        self.last_rpc = rep.reply_to
        outs = [np.asarray(o) for o in rep.payload["outs"]]
        costs = np.asarray(rep.payload["costs"], np.float64)
        return outs, costs

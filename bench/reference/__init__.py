"""Plain references the benchmark compares the program against. They
import nothing of the program.

``router`` is the router's reference. Every other module here is a
member reference: a member's file (``bench/members/<name>.json``) names
its module under ``"reference"``, and :func:`for_member` finds it. A
member reference module keeps this contract:

- ``logits_at(member, params, tokens, read_at, dtype=jnp.float32)``:
  logits (B, P, vocab) at positions ``read_at`` (B, P) of a causal pass
  over ``tokens`` (B, S), in float32 at ``highest`` matmul precision; a
  lower ``dtype`` (bfloat16, or a float8 type) computes the same pass in
  that precision, as the controls of ``bench/correct.py`` read it;
- ``n_active(member) -> int``: the parameters that multiply each token's
  activations, as the chip's share of the member holds them (the step's
  share of the peak, ``readers.step_mfu_pct``, counts 2 FLOPs each);
- ``check_sizes(member, cfg)``: raises ``ValueError`` where the program's
  config ``cfg`` differs from the sizes the member's file states;
- ``smoke_member(member, cfg) -> dict``: the member's file at the
  program's reduced smoke widths ``cfg``, for rehearsals on the CPU.

A member of a new architecture brings a module of its own; no module
here or elsewhere in the benchmark changes for it.
"""
from __future__ import annotations

import importlib

MEMBER_FUNCTIONS = ("logits_at", "n_active", "check_sizes", "smoke_member")


def for_member(member: dict):
    """The reference module that ``member["reference"]`` names."""
    name = member.get("reference")
    where = f"member {member.get('name')!r}, key \"reference\""
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"{where}: {name!r} names no module of "
                         f"bench/reference")
    if name == "router":
        raise ValueError(f"{where}: {name!r} is not a member reference")
    path = f"{__name__}.{name}"
    try:
        module = importlib.import_module(path)
    except ModuleNotFoundError as e:
        if e.name != path:
            raise
        raise ValueError(f"{where}: no module {path}") from None
    missing = [f for f in MEMBER_FUNCTIONS if not hasattr(module, f)]
    if missing:
        raise ValueError(f"{where}: {path} lacks {', '.join(missing)}")
    return module

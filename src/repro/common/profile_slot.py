"""The one slot for the program's wall-clock layer profiler.

Every instrumented site (``models/lm.py``, ``kernels/ops.py``,
``serving/``) reads :func:`active` once and, when it is ``None``, makes
the plain call: no annotation, no clock read, no device sync. This module
imports nothing from the program, so any layer can read it without an
import cycle. The profiler itself is
:class:`repro.obs.profiling.LayerProfiler`.
"""
from __future__ import annotations

_ACTIVE = None


def active():
    """The installed profiler, or None."""
    return _ACTIVE


def install(profiler) -> None:
    """Install ``profiler`` (``None`` removes the one installed). The
    profiler's ``attach()`` runs as it enters the slot and ``detach()`` as
    it leaves, so it listens to JAX's compile events only while
    installed."""
    global _ACTIVE
    if _ACTIVE is not None:
        _ACTIVE.detach()
    _ACTIVE = profiler
    if profiler is not None:
        profiler.attach()

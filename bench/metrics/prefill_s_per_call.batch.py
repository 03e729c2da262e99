"""Seconds from prompt to first token per generate call, from the
program's ``repro.lm.prefill`` spans."""
from bench.program_spans import prefill_s_per_call as read  # noqa: F401

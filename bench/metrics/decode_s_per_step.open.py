"""Seconds of the compiled decode loop per step, from the program's
``repro.lm.decode`` spans and their ``steps``."""
from bench.program_spans import decode_s_per_step as read  # noqa: F401

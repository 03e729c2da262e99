"""Share of the program's ``repro.engine.score`` seconds spent in XLA
compiles charged to those spans or to spans inside them."""
from bench.program_spans import score_compile_pct as read  # noqa: F401

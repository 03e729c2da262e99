"""Share of the program's ``repro.engine.generate`` seconds spent in XLA
compiles charged to those spans or to spans inside them."""
from bench.program_spans import generate_compile_pct as read  # noqa: F401

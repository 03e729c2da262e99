"""The whole generate step's share of the chip's peak: 2 * N_active *
(prompt + generated tokens) over the generate spans' seconds at the peak
FLOP/s of bench/peaks.json."""
from bench.readers import step_mfu_pct as read  # noqa: F401

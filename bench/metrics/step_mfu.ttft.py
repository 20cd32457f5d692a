"""``step_mfu`` again, as the whole step's share of the chip's peak beside
``prefill_roofline``: the same reading, moving the first-token latency."""
from bench.metrics.step_mfu import read  # noqa: F401

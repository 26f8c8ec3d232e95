"""rlc_fallback_wait_ms: mean wait from dispatch to the per-lane launch,
over the verify batches whose rlc batch equation failed.
d(fallback_wait_ns) / d(rlc_fallback) over the window (both booked at
completion; benchmark/lifecycle.py)."""

from benchmark import lifecycle


def read(reading):
    return lifecycle.per_batch_ms(reading, "fallback_wait_ns",
                                  per="rlc_fallback")

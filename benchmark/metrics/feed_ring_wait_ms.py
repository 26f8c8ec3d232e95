"""feed_ring_wait_ms: mean ring wait per retired verify batch: the dwell of
the batch's oldest frag from the source's publish to the stager's drain
of it. d(ring_wait_ns) / d(retired) over the window
(benchmark/lifecycle.py)."""

from benchmark import lifecycle


def read(reading):
    return lifecycle.per_batch_ms(reading, "ring_wait_ns")

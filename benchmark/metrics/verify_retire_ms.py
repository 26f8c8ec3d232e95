"""verify_retire_ms: mean retire time per retired verify batch: found ready
to published (readback, verdict fold, bulk publish). d(retire_ns) /
d(retired) over the window (benchmark/lifecycle.py)."""

from benchmark import lifecycle


def read(reading):
    return lifecycle.per_batch_ms(reading, "retire_ns")

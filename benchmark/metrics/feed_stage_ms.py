"""feed_stage_ms: mean staging time per retired verify batch: the first
staged transaction to the slot's commit (READY). d(stage_ns) / d(retired)
over the window (benchmark/lifecycle.py)."""

from benchmark import lifecycle


def read(reading):
    return lifecycle.per_batch_ms(reading, "stage_ns")

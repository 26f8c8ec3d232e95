"""verify_inflight_ms: mean time in flight per retired verify batch:
dispatch to the completion finding it ready (the rlc program, the
per-lane launch and program where the equation failed, and any wait for
an older batch to retire first). d(inflight_ns) / d(retired) over the
window (benchmark/lifecycle.py)."""

from benchmark import lifecycle


def read(reading):
    return lifecycle.per_batch_ms(reading, "inflight_ns")

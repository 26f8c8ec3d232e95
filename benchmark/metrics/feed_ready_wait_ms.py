"""feed_ready_wait_ms: mean READY wait per retired verify batch: the slot's
commit to its dispatch returning (the queue behind the in-flight cap,
then the host's put and launch). d(ready_wait_ns) / d(retired) over the
window (benchmark/lifecycle.py)."""

from benchmark import lifecycle


def read(reading):
    return lifecycle.per_batch_ms(reading, "ready_wait_ns")

"""rlc_fallback_share: share of the run's batches whose batch equation
failed, so that the per-lane pass ran too.

From the verify tile's fd_flight counters at the end of the run, once
every dispatched batch has completed: rlc_fallback / batches. Not over
the window: `batches` counts at dispatch and `rlc_fallback` at
completion, and with a batch in flight for up to a second at each edge
the window's two deltas do not cover the same batches."""


def read(reading):
    end = reading.get("run_end")
    if not end or end["batches"] <= 0:
        return None
    return end["rlc_fallback"] / end["batches"]

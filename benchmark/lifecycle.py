"""Per-batch means of the verify tile's lifecycle counters over the
window, for the feed_* and verify_* lifecycle metrics.

The program books every retired fd_feed batch once, after its publish,
into its verify lane's fd_flight counters (`retired` and the phase sums
`*_ns`); the window thread snapshots the lane at both edges (c0, c1)."""


def per_batch_ms(reading, counter, per="retired"):
    """d(counter) / d(per) / 1e6 between the window's edges. None where
    the program keeps no lifecycle counters (its lane has no `retired`),
    no batch retired in the window, or d(per) is 0."""
    c0, c1 = reading.get("c0"), reading.get("c1")
    if not c0 or not c1 or "retired" not in c0 or "retired" not in c1:
        return None
    if c1["retired"] - c0["retired"] <= 0:
        return None
    n = c1[per] - c0[per]
    if n <= 0:
        return None
    return (c1[counter] - c0[counter]) / n / 1e6

"""feed_fill_ratio: lanes per dispatched batch over the batch size.

From the verify tile's fd_flight counters at the window's two edges:
(lanes1 - lanes0) / ((batches1 - batches0) * B)."""


def read(reading):
    c0, c1 = reading.get("c0"), reading.get("c1")
    if not c0 or not c1:
        return None
    batches = c1["batches"] - c0["batches"]
    if batches <= 0:
        return None
    return (c1["lanes"] - c0["lanes"]) / (batches * reading["batch"])

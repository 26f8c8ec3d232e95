"""rlc_weights_ms: host time per batch of the rlc weights' draw and
placement (z, and the torsion trials' u, from the host onto the chips
that use them): the engine's counters rlc_weights_ns over
rlc_weights_calls, booked by the async verifier
(ops/verify_rlc.make_async_verifier), between the window's first and
last dispatch (runners/offload_mesh)."""


def read(reading):
    edges = reading.get("weights")
    if not edges:
        return None
    c0, c1 = edges
    if c0["rlc_weights_calls"] is None or c1["rlc_weights_calls"] is None:
        return None
    n = c1["rlc_weights_calls"] - c0["rlc_weights_calls"]
    if n <= 0:
        return None
    return (c1["rlc_weights_ns"] - c0["rlc_weights_ns"]) / n / 1e6

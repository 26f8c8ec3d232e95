"""verify_hop_p99_ms: source stamp (the due time) to the verify tile's
publish, p99 over the frags the tile published inside the window.

The tile's own publish-latency samples (its out link's reservoir,
emptied at the window's start); 32-bit ns, valid below 4.29 s, which
the paced cell's wrap guard holds."""

from benchmark.harness import percentile


def read(reading):
    hop = reading.get("verify_hop_ns")
    if not hop:
        return None
    return percentile(hop, 99) / 1e6

"""source_lag_p99_ms: how late the benchmark's source published.

p99 over the transactions due inside the window and published, of
publish time - due time (benchmark/source.py, 64-bit clock). A late
source shows as a stall that the system did not cause."""

from benchmark.harness import percentile


def read(reading):
    lag = reading.get("source_lag_ns")
    if not lag:
        return None
    return percentile(lag, 99) / 1e6

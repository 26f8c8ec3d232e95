"""kernel_ms.pod_local: device time of the sharded rlc engine's local
program per batch per chip (benchmark/chips.module_ms). The program is
the split pair's local half (parallel/mesh.py: s-range, decompress,
front end, status ladder and the three bucket fills over the chip's own
lanes, no collective), named jit_pod_local on the trace's module
line."""

from benchmark import chips


def read(reading):
    return chips.module_ms(reading, "jit_pod_local")

"""kernel_ms.pod_tail: device time of the sharded rlc engine's combine
program per batch per chip (benchmark/chips.module_ms). The program is
the split pair's tail (parallel/mesh.py: one all_gather of the chips'
partials over the interconnect, the doubling-chain tails and the
global verdict), named jit_pod_tail on the trace's module line."""

from benchmark import chips


def read(reading):
    return chips.module_ms(reading, "jit_pod_tail")

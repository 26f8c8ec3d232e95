"""kernel_ms.msm: device time of the Pippenger MSM kernels (ops/msm_pallas.py) per rlc batch.

The time, inside the traced window, of the device ops named in OPS, over
the number of rlc programs (XLA modules whose name contains
"verify_batch_rlc") that started in the window.

Names as the chip's trace gives them today: every Pallas kernel of the
rlc program is an HLO custom-call named verify_batch_rlc.<n>, and the
trace carries no kernel name. In program order (the lowered module's
kernel_name attributes, matched one to one with a compile of the same
program for a described v5e) they are .11 _decompress_niels_kernel,
.12 _frontend_rlc_kernel, then .13 to .21 the nine kernels of the
three Pippenger MSMs (bucket fills, aggregations, torsion grid). The
trace has no stable kernel names yet. So the reader reads only where
the window holds exactly these eleven custom-calls of the rlc program
(EXPECTED): a program change that adds, removes or renumbers a kernel
leaves the metric out of the line instead of timing another kernel
under its name, and OPS is then derived again the same way.
"""

from benchmark import trace

OPS = ('verify_batch_rlc.13', 'verify_batch_rlc.14', 'verify_batch_rlc.15', 'verify_batch_rlc.16', 'verify_batch_rlc.17', 'verify_batch_rlc.18', 'verify_batch_rlc.19', 'verify_batch_rlc.20', 'verify_batch_rlc.21')

EXPECTED = {f"verify_batch_rlc.{n}" for n in range(11, 22)}


def read(reading):
    red = reading.get("trace")
    if not red or trace.numbered_ops(red, "verify_batch_rlc") != EXPECTED:
        return None
    n = trace.module_count(red, ("verify_batch_rlc",))
    t = trace.op_time_s(red, OPS)
    if n <= 0 or t <= 0:
        return None
    return 1e3 * t / n

"""The fused count+score kernel's share of its roofline.

Least time is the larger of the counting FLOPs over the bf16 peak and the
bytes over the HBM peak; measured time is the summed device time of the
kernel's events in the traced window. Per column subset sigma of the n
columns (|sigma| <= s) counting is a (q^|sigma| x m) one-hot times the
(m x n q) one-hot of every column: 2 q^|sigma| m n q FLOPs, exact in bf16
for 0/1 operands. Bytes are the subset's m configuration codes read and
its n scores written, 4 bytes each. The log-gamma scoring that follows the
counts has no published peak and is not in the bound.
"""
import math

KERNEL = "%fused_scores_pallas"


def work_per_table(n: int, m: int, q: int, s: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one dense table build's counting."""
    flops = sum(math.comb(n, k) * 2 * q ** k * m * n * q
                for k in range(s + 1))
    nbytes = sum(math.comb(n, k) for k in range(s + 1)) * (m + n) * 4
    return flops, nbytes


def read(trace, counters, config, peak):
    t_ns = sum(v for k, v in trace.op_total_ns.items()
               if k.startswith(KERNEL))
    builds = counters.get("traced_builds", 0)
    if t_ns <= 0 or builds <= 0:
        return None
    flops, nbytes = work_per_table(counters["n"], counters["m"],
                                   counters["q"], counters["s"])
    least_s = builds * max(flops / peak["bf16_flops_per_s"],
                           nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (t_ns * 1e-9)

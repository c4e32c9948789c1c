"""The BGe scorer's share of its roofline.

Least time is the larger of the scorer's FLOPs over the bf16 peak and its
bytes over the HBM peak; measured time is the summed device time of the
scorer's events (``%bge_scores*``, the Pallas kernel) in the traced window.
Per column subset sigma of the n columns with l = |sigma| <= s: the
Cholesky of R_sigma,sigma (l^3/3 FLOPs), the triangular solve against the
rows R_sigma,: of all n children (l^2 n) and the Schur complement's
squares and sums (2 l n). It moves 4 (n + l) bytes: the n scores written
and the l column ids read. Both come from the configuration's n and s, not
from what the program computes: padding to s columns is not work.
"""
import math

KERNEL = "%bge_scores"


def work_per_table(n: int, s: int) -> tuple[float, int]:
    """(FLOPs, bytes) of one dense table build's BGe scoring."""
    flops = sum(math.comb(n, l) * (l ** 3 / 3 + l * l * n + 2 * l * n)
                for l in range(s + 1))
    nbytes = sum(math.comb(n, l) * 4 * (n + l) for l in range(s + 1))
    return flops, nbytes


def read(trace, counters, config, peak):
    t_ns = sum(v for k, v in trace.op_total_ns.items()
               if k.startswith(KERNEL))
    builds = counters.get("traced_builds", 0)
    if t_ns <= 0 or builds <= 0:
        return None
    flops, nbytes = work_per_table(config["n"], config["s"])
    least_s = builds * max(flops / peak["bf16_flops_per_s"],
                           nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (t_ns * 1e-9)

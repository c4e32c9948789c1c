"""The fused count+score kernel's share of its roofline at per-variable
arities.

Least time is the larger of the counting FLOPs over the bf16 peak and the
bytes over the HBM peak; measured time is the summed device time of the
kernel's events in the traced window. Per column subset sigma of the n
columns (|sigma| <= s) counting is a (q_sigma x m) one-hot times the
(m x R) one-hot of every column's states, R = sum_i r_i: 2 q_sigma m R
FLOPs, with q_sigma = prod_{j in sigma} r_j. Bytes are the subset's m
configuration codes read and its n scores written, 4 bytes each. Both come
from the configuration's arity vector ``q``, not from what the program
computes: bins padded past q_sigma are not real work. The log-gamma scoring
that follows the counts has no published peak and is not in the bound.
"""
import math

KERNEL = "%fused_scores_pallas"


def work_per_table(r: list, m: int, s: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one dense table build's counting at arities r."""
    e = [1] + [0] * s           # e[k]: sum of q_sigma over subsets of size k
    for x in r:
        for k in range(s, 0, -1):
            e[k] += e[k - 1] * x
    n = len(r)
    flops = 2 * m * sum(r) * sum(e)
    nbytes = sum(math.comb(n, k) for k in range(s + 1)) * (m + n) * 4
    return flops, nbytes


def read(trace, counters, config, peak):
    t_ns = sum(v for k, v in trace.op_total_ns.items()
               if k.startswith(KERNEL))
    builds = counters.get("traced_builds", 0)
    if t_ns <= 0 or builds <= 0:
        return None
    q, n = config["q"], config["n"]
    r = list(q) if isinstance(q, list) else [q] * n
    flops, nbytes = work_per_table(r, config["m"], config["s"])
    least_s = builds * max(flops / peak["bf16_flops_per_s"],
                           nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (t_ns * 1e-9)

"""Per-iteration order scoring's share of its HBM roofline.

Least time of one iteration (every chain one proposal) is the bytes the
algorithm needs over the HBM peak; measured time is the segment program's
device time in the traced window over the iterations it ran. Per chain the
bitmask delta rescore needs the window's w table rows (w S 4 bytes), its w
nodes' violation planes read and written (2 w P ceil(S/32) 4) and w
membership rows (w ceil(S/32) 4), with P = ceil(log2(s + 1)) planes and S
unpadded. The same work counts whatever implements it.
"""
import math

MODULE = "jit_run_segment"


def bytes_per_iteration(chains: int, S: int, s: int, w: int) -> int:
    words = math.ceil(S / 32)
    planes = math.ceil(math.log2(s + 1))
    return chains * (w * S * 4 + 2 * w * planes * words * 4 + w * words * 4)


def read(trace, counters, config, peak):
    t_ns = trace.module_ns.get(MODULE, 0.0)
    steps = counters.get("traced_steps", 0)
    if t_ns <= 0 or steps <= 0:
        return None
    least_s = bytes_per_iteration(counters["chains"], counters["S"],
                                  counters["s"], counters["window"]) \
        / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (t_ns * 1e-9 / steps)

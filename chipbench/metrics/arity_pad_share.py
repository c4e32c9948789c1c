"""Share of the bins the count+score kernel computes that cannot count:
1 - bins_real / bins_computed, from the build's counters. ``bins_real`` is
the sum of q_sigma over the column subsets, ``bins_computed`` the sum over
chunks of chunk rows times the chunk's bucket bin count, so padding rows
and bins past a subset's q_sigma are the waste."""


def read(trace, counters, config, peak):
    real = counters.get("bins_real")
    computed = counters.get("bins_computed")
    if not real or not computed:
        return None
    return 100.0 * (1.0 - real / computed)

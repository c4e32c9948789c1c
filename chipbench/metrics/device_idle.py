"""Share of the traced window in which no operation ran on the cell's
devices: 1 - union of device-busy intervals / window. It reads
``device_idle.mcmc`` and ``device_idle.preprocess`` alike, one name for
each end-to-end metric it moves."""


def read(trace, counters, config, peak):
    return 100.0 * trace.idle_share if trace.window_ns > 0 else None

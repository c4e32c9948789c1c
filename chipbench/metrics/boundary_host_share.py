"""Share of each supervised segment's wall time that the host spends while
no segment is queued: 100 (1 - sum ``segment.wait`` s / sum ``segment`` s)
over the traced window, from the program's own spans
(``repro.telemetry.spans``). ``segment`` covers one
``RunSupervisor.advance()``; ``segment.wait`` is the drain's wait for that
segment's result. The rest is dispatch, the fetch, the collector's check
and the supervisor's bookkeeping, while the drain leaves the device
nothing queued."""


def read(trace, counters, config, peak):
    try:
        from repro.telemetry.spans import snapshot
    except ImportError:                    # a program without the spans
        return None
    spans = snapshot()["spans"]
    seg, wait = spans.get("segment"), spans.get("segment.wait")
    if seg is None or wait is None or seg["s"] <= 0:
        return None
    return 100.0 * (1.0 - wait["s"] / seg["s"])

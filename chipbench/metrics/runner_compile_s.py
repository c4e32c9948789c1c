"""Backend-compile seconds the program spent inside ``segment`` spans (one
``RunSupervisor.advance()`` each, outside any narrower span) over the whole
process: the segment runner's compile in the warm segments. The program's
compile counter (``repro.telemetry.spans``) is always on, as set-up is
never traced. The runner carries its dataset's table, so the compile cache
never serves it and every run pays it."""


def read(trace, counters, config, peak):
    try:
        from repro.telemetry.spans import snapshot
    except ImportError:                    # a program without the spans
        return None
    seg = snapshot()["compile"].get("segment")
    return seg["s"] if seg is not None and seg["s"] > 0 else None

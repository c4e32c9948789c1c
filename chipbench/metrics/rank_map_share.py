"""Share of the traced builds' wall time spent ranking every node's parent
sets on the host: 100 sum ``preprocess.rank_map`` s / sum
``preprocess.build`` s, from the program's own spans
(``repro.telemetry.spans``). ``preprocess.build`` covers a dense
``build_score_table_fused`` call; ``preprocess.rank_map`` the numpy
``_rank_map`` over n x S parent sets inside its assembly."""


def read(trace, counters, config, peak):
    try:
        from repro.telemetry.spans import snapshot
    except ImportError:                    # a program without the spans
        return None
    spans = snapshot()["spans"]
    build = spans.get("preprocess.build")
    rank = spans.get("preprocess.rank_map")
    if build is None or rank is None or build["s"] <= 0:
        return None
    return 100.0 * rank["s"] / build["s"]

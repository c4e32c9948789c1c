"""Share of the traced builds' preprocessing time spent in host planning
and assembly: (sum plan_s + sum assemble_s) / sum preprocess_s, from the
stage times ``build_score_table_fused(return_info=True)`` reports.
``assemble_s`` does not wait for the device gather, so it times the host
rank map and the gather's dispatch."""


def read(trace, counters, config, peak):
    total = counters.get("traced_preprocess_s", 0.0)
    if total <= 0:
        return None
    return 100.0 * (counters["traced_plan_s"]
                    + counters["traced_assemble_s"]) / total

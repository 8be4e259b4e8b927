"""Ratio of two `engine_stats()` counters, differenced over the window.
Where the program under test has not got one of them (an older program,
as the parent of the PR that adds a counter) there is nothing to read."""


def read(evidence, metric):
    eng = (evidence["out"].get("counters") or {}).get("engine") or {}
    if metric["num"] not in eng or not eng.get(metric["den"]):
        return None
    return metric.get("scale", 1.0) * eng[metric["num"]] / eng[metric["den"]]

"""`engine_ratio` for counters a later PR gave the program: where the
program under test has no such counter (an older one, as the parent of the
PR that added it), there is nothing to read; `engine_ratio` itself raises
when only its numerator is missing."""


def read(evidence, metric):
    eng = (evidence["out"].get("counters") or {}).get("engine") or {}
    if metric["num"] not in eng or not eng.get(metric["den"]):
        return None
    return metric.get("scale", 1.0) * eng[metric["num"]] / eng[metric["den"]]

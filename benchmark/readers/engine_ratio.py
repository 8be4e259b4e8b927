"""Ratio of two `engine_stats()` counters, differenced over the window."""


def read(evidence, metric):
    eng = (evidence["out"].get("counters") or {}).get("engine")
    if not eng or not eng.get(metric["den"]):
        return None
    return metric.get("scale", 1.0) * eng[metric["num"]] / eng[metric["den"]]

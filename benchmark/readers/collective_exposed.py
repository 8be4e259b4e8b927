"""Exposed collective milliseconds per traced step (mean over chips)."""


def read(evidence, metric):
    trace, out = evidence["trace"], evidence["out"]
    if "collective_exposed_s" not in trace or not out.get("trace_steps"):
        return None
    return 1000.0 * trace["collective_exposed_s"] / out["trace_steps"]

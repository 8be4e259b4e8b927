"""Device busy milliseconds per traced train step (mean over chips)."""


def read(evidence, metric):
    trace, out = evidence["trace"], evidence["out"]
    if not trace.get("busy_s") or not out.get("trace_steps"):
        return None
    return 1000.0 * trace["busy_s"] / out["trace_steps"]

"""Device time of the executions of the XLA programs whose name matches
`metric["module_pattern"]`, per unit of their work: "substep" (executions
x decode_chunk) or "padded_ktok" (padded prompt tokens / 1000)."""
from benchmark.harness import xplane


def read(evidence, metric):
    trace = evidence["trace"]
    ex = xplane.module_executions(trace, metric["module_pattern"])
    seconds, count = ex["seconds"], ex["count"]
    if not count:
        return None
    if metric["per"] == "substep":
        units = count * evidence["out"]["info"]["decode_chunk"]
    elif metric["per"] == "padded_ktok":
        units = trace.get("padded_prefill_tokens", 0) / 1000.0
    else:
        raise ValueError(f"unknown unit of work {metric['per']!r}")
    if not units:
        return None
    return metric.get("scale", 1.0) * seconds / units

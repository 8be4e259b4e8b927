"""Share of device busy time spent in the operations whose name matches
`metric["op_pattern"]`."""
from benchmark.harness import xplane


def read(evidence, metric):
    trace = evidence["trace"]
    if not trace.get("busy_s"):
        return None
    k = xplane.op_seconds_matching(trace, metric["op_pattern"])
    return 100.0 * k / trace["busy_s"] if k else None

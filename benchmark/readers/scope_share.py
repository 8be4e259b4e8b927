"""Share of device busy time spent in the operations traced under a
`jax.named_scope` that `metric["scope_pattern"]` is found in. The scope of
an operation comes from the compiled step's own text (`out["op_scopes"]`,
`xplane.op_scopes`), never from a list of instruction numbers."""
from benchmark.harness import xplane


def read(evidence, metric):
    trace, scopes = evidence["trace"], evidence["out"].get("op_scopes")
    if not trace.get("busy_s") or not scopes:
        return None
    k = xplane.scope_seconds_matching(trace, scopes, metric["scope_pattern"])
    return 100.0 * k / trace["busy_s"] if k else None

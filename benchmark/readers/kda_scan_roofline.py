"""The gated delta-rule scan's share of its roofline over the traced
steps: least time the peaks table allows for the scan's REQUIRED work,
over the device self time of the operations traced under the
`jax.named_scope` that `metric["scope_pattern"]` is found in (the scan is
one named thing in the trace: `kda.scan`).

Required, per traced step and KDA layer: one forward and one backward of
the recurrence over [rows, seq, kda_heads] at the KDA head width (the
remat's second forward, and whatever a chunked form spends beyond the
recurrence's own arithmetic, are not required work). The operations and
bytes are the configuration's architecture module's
(`kda_scan_cost(batch, heads, seq, dk, dv, backward=)`), the KDA layers
those its `layer_kinds(conf, n_layers)` names. A program without such a
scope (the parent of the PR that brought this file), or an architecture
without those two functions, gives nothing to read."""
from benchmark.harness import flops, spec, xplane


def read(evidence, metric):
    trace, out = evidence["trace"], evidence["out"]
    peaks, f, t = evidence.get("peaks"), evidence["fields"], \
        evidence["traffic"]
    scopes = out.get("op_scopes")
    arch = spec.load_architecture(evidence["conf"],
                                  evidence.get("root", spec.ROOT))
    cost_of = getattr(arch, "kda_scan_cost", None)
    kinds_of = getattr(arch, "layer_kinds", None)
    if not scopes or not peaks or not out.get("trace_steps") \
            or cost_of is None or kinds_of is None:
        return None
    k = xplane.scope_seconds_matching(trace, scopes, metric["scope_pattern"])
    layers = kinds_of(evidence["conf"], f["n_layers"]).count("kda")
    if not k or not layers:
        return None
    cost = {"flops": 0.0, "bytes": 0.0}
    for backward in (False, True):
        c = cost_of(t["rows"], f["kda_heads"], t["seq_len"],
                    f["kda_head_dim"], f["kda_head_dim"], backward=backward)
        cost["flops"] += c["flops"]
        cost["bytes"] += c["bytes"]
    scale = layers * out["trace_steps"] / evidence["cell"]["chips"]
    least = flops.roofline_seconds(cost["flops"] * scale,
                                   cost["bytes"] * scale, peaks)
    if least["bound"] != metric["bound"]:
        raise ValueError(f"{metric.get('name')}: the metric file says the "
                         f"{metric['bound']} bound applies, the peaks "
                         f"table says {least['bound']}")
    return 100.0 * least["seconds"] / k

"""The attention kernel's share of its roofline over the traced steps:
least time for the REQUIRED work (per layer and step one forward and one
backward over [rows, seq, heads, head_dim], this chip's share of rows and
heads) by the peaks table, over the kernel events' device time. The
width of a query/key head is `fields["head_dim"]` and that of a value
head `fields["v_head_dim"]`, where the architecture's `fields` gives
them (heads that are not hidden / heads wide, latent attention);
otherwise hidden / heads, as the program derives it."""
from benchmark.harness import flops, xplane


def read(evidence, metric):
    trace, out = evidence["trace"], evidence["out"]
    peaks, f, t = evidence.get("peaks"), evidence["fields"], \
        evidence["traffic"]
    k = xplane.op_seconds_matching(trace, metric["op_pattern"])
    if not k or not peaks or not out.get("trace_steps"):
        return None
    hd = f.get("head_dim") or f["d_model"] // f["n_heads"]
    cost = {"flops": 0.0, "bytes": 0.0}
    for backward in (False, True):
        c = flops.flash_attention_cost(
            t["rows"], f["n_heads"], t["seq_len"], t["seq_len"], hd,
            causal=True, backward=backward,
            v_head_dim=f.get("v_head_dim"))
        cost["flops"] += c["flops"]
        cost["bytes"] += c["bytes"]
    scale = f["n_layers"] * out["trace_steps"] / evidence["cell"]["chips"]
    least = flops.roofline_seconds(cost["flops"] * scale,
                                   cost["bytes"] * scale, peaks)
    if least["bound"] != metric["bound"]:
        raise ValueError(f"{metric.get('name')}: the metric file says the "
                         f"{metric['bound']} bound applies, the peaks "
                         f"table says {least['bound']}")
    return 100.0 * least["seconds"] / k

"""The attention kernel's share of its roofline over the traced steps, for
a model whose step calls the kernel in more places than its main stack:
least time for the REQUIRED work by the peaks table over the kernel
events' device time.

Required, per traced step: one forward and one backward (the remat's
second forward is not required work) over [rows, seq, heads] at the head
widths `fields["head_dim"]` (query/key) and `fields["v_head_dim"]` (value)
in each of the `n_layers` layers of the main stack (leading dense layers
and expert layers alike), and over [rows, seq - 1, heads] in each of the
`mtp_layers` prediction modules (the positions that have a token after
next). `readers/kernel_roofline.py` multiplies ONE call by `n_layers`,
which is this count only for a model without such a module."""
from benchmark.harness import flops, xplane


def required(fields: dict, traffic: dict) -> dict:
    """FLOPs and bytes of the attention kernels of ONE step."""
    f, t = fields, traffic
    hd = f.get("head_dim") or f["d_model"] // f["n_heads"]
    cost = {"flops": 0.0, "bytes": 0.0}
    for calls, seq in ((f["n_layers"], t["seq_len"]),
                       (f.get("mtp_layers", 0), t["seq_len"] - 1)):
        for backward in (False, True):
            c = flops.flash_attention_cost(
                t["rows"], f["n_heads"], seq, seq, hd, causal=True,
                backward=backward, v_head_dim=f.get("v_head_dim"))
            cost["flops"] += calls * c["flops"]
            cost["bytes"] += calls * c["bytes"]
    return cost


def read(evidence, metric):
    trace, out = evidence["trace"], evidence["out"]
    peaks = evidence.get("peaks")
    k = xplane.op_seconds_matching(trace, metric["op_pattern"])
    if not k or not peaks or not out.get("trace_steps"):
        return None
    cost = required(evidence["fields"], evidence["traffic"])
    scale = out["trace_steps"] / evidence["cell"]["chips"]
    least = flops.roofline_seconds(cost["flops"] * scale,
                                   cost["bytes"] * scale, peaks)
    if least["bound"] != metric["bound"]:
        raise ValueError(f"{metric.get('name')}: the metric file says the "
                         f"{metric['bound']} bound applies, the peaks "
                         f"table says {least['bound']}")
    return 100.0 * least["seconds"] / k

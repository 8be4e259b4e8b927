"""The MoE grouped matmuls' share of their roofline over the traced steps:
least time for the REQUIRED work by the peaks table, over the device time of
the operations whose name matches `metric["op_pattern"]`.

Required, per traced step and layer: three passes (forward, gradient for
the rows, gradient for the weights; the remat's second forward is not
required work) over the three matrices of the SwiGLU (gate, up, down), each
a product of M = rows x seq x moe_top_k routed rows with one d_model x d_ff
matrix per expert: 2 x M x d_model x d_ff FLOPs. Bytes, per pass and
matrix: the experts' weights once (read, or written as their gradient) plus
the rows in and the rows out, at `bytes_per_elem` each."""
from benchmark.harness import flops, xplane


def required(fields: dict, traffic: dict, bytes_per_elem: int = 2) -> dict:
    """FLOPs and bytes of the grouped matmuls of ONE layer in ONE step."""
    d, f = fields["d_model"], fields["d_ff"]
    m = traffic["rows"] * traffic["seq_len"] * fields["moe_top_k"]
    calls = 3 * 3    # passes x matrices
    return {"flops": calls * 2.0 * m * d * f,
            "bytes": calls * float(bytes_per_elem) * (
                fields["moe_experts"] * d * f + m * d + m * f)}


def read(evidence, metric):
    trace, out = evidence["trace"], evidence["out"]
    peaks, f = evidence.get("peaks"), evidence["fields"]
    k = xplane.op_seconds_matching(trace, metric["op_pattern"])
    if not k or not peaks or not out.get("trace_steps") \
            or not f.get("moe_experts"):
        return None
    cost = required(f, evidence["traffic"])
    scale = f["n_layers"] * out["trace_steps"] / evidence["cell"]["chips"]
    least = flops.roofline_seconds(cost["flops"] * scale,
                                   cost["bytes"] * scale, peaks)
    if least["bound"] != metric["bound"]:
        raise ValueError(f"{metric.get('name')}: the metric file says the "
                         f"{metric['bound']} bound applies, the peaks "
                         f"table says {least['bound']}")
    return 100.0 * least["seconds"] / k

"""The MoE grouped matmuls' share of their roofline over the traced steps,
for ONE chip's share of an expert-parallel layer: least time for the
REQUIRED work by the peaks table, over the device time of the operations
whose name matches `metric["op_pattern"]`.

This chip is asked to multiply the rows that FELL ON ITS OWN experts, not
every routed row (`readers/grouped_matmul_roofline.py` counts rows x seq x
top-k rows against every expert's weights, which is a chip that holds all
of them). Required, per traced step and layer that has experts (the main
stack's expert layers and each prediction module's): three passes
(forward, gradient for the rows, gradient for the weights; the remat's
second forward is not required work) over the three matrices of the
SwiGLU, each a product of M = rows x seq x top-k x held-share rows with one
d_model x d_ff matrix per HELD expert: 2 x M x d_model x d_ff FLOPs. The
held share is the step's own counter (`step_metrics.moe_held_share`: the
assignments that fell on held experts over all, mean over those layers
and over the window's steps; 1/8 at balance with 8 of 64 held). The rows
of the [rows x seq x top-k] buffer past them belong to no group and are
no required work. Bytes, per pass and matrix: the held experts' weights
once plus the M rows in and out, at `bytes_per_elem` each."""
from benchmark.harness import flops, xplane
from benchmark.harness.spec import dig


def required(fields: dict, traffic: dict, held_share: float,
             bytes_per_elem: int = 2) -> dict:
    """FLOPs and bytes of the grouped matmuls of ONE layer in ONE step."""
    d, f = fields["d_model"], fields["d_ff"]
    held = fields.get("moe_held_experts") or fields["moe_experts"]
    m = traffic["rows"] * traffic["seq_len"] * fields["moe_top_k"] \
        * held_share
    calls = 3 * 3    # passes x matrices
    return {"flops": calls * 2.0 * m * d * f,
            "bytes": calls * float(bytes_per_elem) * (
                held * d * f + m * d + m * f)}


def read(evidence, metric):
    trace, out = evidence["trace"], evidence["out"]
    peaks, f = evidence.get("peaks"), evidence["fields"]
    k = xplane.op_seconds_matching(trace, metric["op_pattern"])
    share = dig(out, "step_metrics.moe_held_share")
    if not k or not peaks or not out.get("trace_steps") or not share:
        return None
    cost = required(f, evidence["traffic"], share)
    layers = f["n_layers"] - f.get("moe_dense_layers", 0) \
        + f.get("mtp_layers", 0)
    scale = layers * out["trace_steps"] / evidence["cell"]["chips"]
    least = flops.roofline_seconds(cost["flops"] * scale,
                                   cost["bytes"] * scale, peaks)
    if least["bound"] != metric["bound"]:
        raise ValueError(f"{metric.get('name')}: the metric file says the "
                         f"{metric['bound']} bound applies, the peaks "
                         f"table says {least['bound']}")
    return 100.0 * least["seconds"] / k

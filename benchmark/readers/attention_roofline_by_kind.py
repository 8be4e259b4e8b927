"""The attention kernel's share of its roofline over the traced steps, for
a model in which only SOME layers call it (a period of mixer kinds): least
time for the REQUIRED work by the peaks table over the kernel events'
device time.

Required, per traced step and FULL-ATTENTION layer (those the
configuration's architecture module's `layer_kinds(conf, n_layers)` calls
"attention"): one forward and one backward (the remat's second forward is
not required work) over [rows, seq, heads] at the query/key head width
`nope_head_dim + rope_head_dim` (else `head_dim`, else hidden / heads) and
the value head width `v_head_dim`; zero columns a narrower value head is
padded with outside the kernel are not required work.
`readers/kernel_roofline.py` multiplies ONE call by `n_layers`, which is
this count only for a model whose every layer is attention. An
architecture without `layer_kinds` gives nothing to read."""
from benchmark.harness import flops, spec, xplane


def read(evidence, metric):
    trace, out = evidence["trace"], evidence["out"]
    peaks, f, t = evidence.get("peaks"), evidence["fields"], \
        evidence["traffic"]
    k = xplane.op_seconds_matching(trace, metric["op_pattern"])
    kinds_of = getattr(spec.load_architecture(
        evidence["conf"], evidence.get("root", spec.ROOT)),
        "layer_kinds", None)
    if not k or not peaks or not out.get("trace_steps") or kinds_of is None:
        return None
    layers = kinds_of(evidence["conf"], f["n_layers"]).count("attention")
    if not layers:
        return None
    hd = f["nope_head_dim"] + f["rope_head_dim"] if f.get("nope_head_dim") \
        else f.get("head_dim") or f["d_model"] // f["n_heads"]
    cost = {"flops": 0.0, "bytes": 0.0}
    for backward in (False, True):
        c = flops.flash_attention_cost(
            t["rows"], f["n_heads"], t["seq_len"], t["seq_len"], hd,
            causal=True, backward=backward, v_head_dim=f.get("v_head_dim"))
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

"""Model FLOP/s utilisation over the traced steps: required FLOPs per
token (three times what the configuration's architecture module counts
for its forward pass) x the tokens of the `jit_step` executions in the
trace over the trace's window (device clock), over chips x peak. The
traced run's own end-to-end tokens/s is not used: starting and stopping
the profiler sits inside that run's window."""
from benchmark.harness import flops, spec


def read(evidence, metric):
    trace, out, t = evidence["trace"], evidence["out"], evidence["traffic"]
    if not evidence.get("peaks") or not out.get("trace_steps") \
            or not trace.get("window_s"):
        return None
    tokens_per_s = out["trace_steps"] * t["rows"] * t["seq_len"] \
        / trace["window_s"]
    arch = spec.load_architecture(evidence["conf"],
                                  evidence.get("root", spec.ROOT))
    per_token = flops.train_from_forward(arch.forward_flops_per_token(
        evidence["fields"], evidence["conf"], t["seq_len"]))
    return flops.mfu_percent(per_token, tokens_per_s,
                             evidence["cell"]["chips"], evidence["peaks"])

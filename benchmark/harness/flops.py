"""Operations and bytes an algorithm REQUIRES, from shapes alone. Kept with
the benchmark so that no PR that claims a gain can change the yardstick.
Recomputed operations (rematerialisation, the masked half of a causal
score matrix a kernel happens to compute) never count: a program that does
more arithmetic than required gets a lower utilisation, not a higher one.
For sparse experts: the experts a token is routed to, plus the router.

What one architecture's forward pass requires per token
(`forward_flops_per_token`, `num_params`) is in its own file,
`benchmark/architectures/<name>.py`; here is what no architecture owns.
"""

from __future__ import annotations


def train_from_forward(forward_flops: float) -> float:
    """Forward plus backward: the backward pass needs twice the forward's
    multiplications (gradients for inputs and for weights), whatever the
    block."""
    return 3.0 * forward_flops


def flash_attention_cost(batch: int, heads: int, seq_q: int, seq_k: int,
                         head_dim: int, *, causal: bool = True,
                         backward: bool = False,
                         bytes_per_elem: int = 2) -> dict:
    """One call of the fused attention kernel family on [batch, seq,
    heads, head_dim] operands (K/V already expanded to `heads`, as the
    caller hands them over).

    forward: QK^T and PV, 2*Tq*Tk*hd FLOPs each per (batch, head);
    backward: recompute QK^T, then dV, dP, dQ, dK: five such products
    (FlashAttention-2). Causality needs half of each. Bytes: each operand
    read once, each result written once; the float32 logsumexp / delta
    rows are counted at 4 bytes.
    """
    pairs = batch * heads
    per_product = 2.0 * seq_q * seq_k * head_dim * (0.5 if causal else 1.0)
    q_elems = pairs * seq_q * head_dim
    k_elems = pairs * seq_k * head_dim
    rows = pairs * seq_q * 4
    if not backward:
        flops = 2 * per_product * pairs
        nbytes = (q_elems + 2 * k_elems) * bytes_per_elem \
            + q_elems * bytes_per_elem + rows          # o, lse
    else:
        flops = 5 * per_product * pairs
        nbytes = (2 * q_elems + 2 * k_elems) * bytes_per_elem \
            + q_elems * bytes_per_elem + 2 * rows \
            + (q_elems + 2 * k_elems) * bytes_per_elem  # dq, dk, dv
        # reads: q, do, k, v, o (for delta), lse, delta
    return {"flops": flops, "bytes": float(nbytes)}


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """Least time one chip could take, and which peak sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}


def mfu_percent(flops_per_token: float, tokens_per_s: float, chips: int,
                peaks: dict) -> float:
    return 100.0 * flops_per_token * tokens_per_s \
        / (chips * peaks["bf16_flops_per_s"])

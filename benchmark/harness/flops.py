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
                         bytes_per_elem: int = 2,
                         v_head_dim: int | None = None) -> dict:
    """One call of the fused attention kernel family on [batch, seq,
    heads, head_dim] operands (K/V already expanded to `heads`, as the
    caller hands them over). ``head_dim`` is the width of a query and a
    key head, ``v_head_dim`` that of a value (and output) head where the
    two differ (latent attention); absent, they are equal.

    forward: QK^T, 2*Tq*Tk*head_dim FLOPs per (batch, head), and PV,
    2*Tq*Tk*v_head_dim; backward: recompute QK^T, then dV, dP (the value
    width), dQ, dK (the query/key width): five such products
    (FlashAttention-2). Causality needs half of each. Bytes: each operand
    read once, each result written once; the float32 logsumexp / delta
    rows are counted at 4 bytes.
    """
    vd = head_dim if v_head_dim is None else v_head_dim
    pairs = batch * heads
    half = 0.5 if causal else 1.0
    qk_product = 2.0 * seq_q * seq_k * head_dim * half
    pv_product = 2.0 * seq_q * seq_k * vd * half
    q_elems = pairs * seq_q * head_dim
    k_elems = pairs * seq_k * head_dim
    v_elems = pairs * seq_k * vd
    o_elems = pairs * seq_q * vd
    rows = pairs * seq_q * 4
    if not backward:
        flops = (qk_product + pv_product) * pairs
        nbytes = (q_elems + k_elems + v_elems) * bytes_per_elem \
            + o_elems * bytes_per_elem + rows          # o, lse
    else:
        flops = (3 * qk_product + 2 * pv_product) * pairs
        nbytes = (q_elems + o_elems + k_elems + v_elems) * bytes_per_elem \
            + o_elems * bytes_per_elem + 2 * rows \
            + (q_elems + k_elems + v_elems) * bytes_per_elem  # dq, dk, dv
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

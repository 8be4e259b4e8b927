"""Operations and bytes an algorithm REQUIRES, from shapes alone. Kept with
the benchmark so that no PR that claims a gain can change the yardstick.
Recomputed operations (rematerialisation, the masked half of a causal
score matrix a kernel happens to compute) never count: a program that does
more arithmetic than required gets a lower utilisation, not a higher one.

``fields`` is the TransformerConfig field dict `spec.transformer_fields`
makes from a config file.
"""

from __future__ import annotations


def matmul_params(fields: dict) -> dict:
    """Weights that take part in a matrix multiplication per token: the
    embedding lookup is a gather and does no arithmetic; norms are
    elementwise and left out."""
    d, ff = fields["d_model"], fields["d_ff"]
    H = fields["n_heads"]
    KV = fields.get("n_kv_heads") or H
    hd = d // H
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return {"per_layer": per_layer, "head": d * fields["vocab_size"],
            "total": fields["n_layers"] * per_layer
            + d * fields["vocab_size"]}


def num_params(fields: dict) -> int:
    """All weights held (embedding, blocks with their two norms, final
    norm, untied head)."""
    d, v, L = fields["d_model"], fields["vocab_size"], fields["n_layers"]
    mm = matmul_params(fields)
    head = 0 if fields.get("tie_embeddings") else d * v
    return v * d + L * (mm["per_layer"] + 2 * d) + d + head


def forward_flops_per_token(fields: dict, seq_len: int) -> float:
    """2 FLOPs per weight that multiplies, plus causal attention: QK^T and
    PV are each 2*T*hd per head and query, of which causality needs half
    (a query at position t attends t+1 keys; mean (T+1)/2)."""
    d, L = fields["d_model"], fields["n_layers"]
    attn = L * 2 * 2 * d * (seq_len + 1) / 2
    return 2.0 * matmul_params(fields)["total"] + attn


def train_flops_per_token(fields: dict, seq_len: int) -> float:
    """Forward plus backward: the backward pass needs twice the forward's
    multiplications (gradients for inputs and for weights)."""
    return 3.0 * forward_flops_per_token(fields, seq_len)


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

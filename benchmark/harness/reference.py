"""The comparison that decides `correct`, for every architecture: the
program's logits and loss against those of the configuration's plain
reference (`benchmark/architectures/<name>.py`, found by
`spec.load_architecture`; float32, matmuls at "highest" precision, written
from the published description of the block), by a relative RMS error
held to a tolerance that depends only on the compute dtype the
configuration STATES.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def reference_loss(logits, targets):
    """Mean next-token cross entropy: logits [T, V] against targets [T]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.asarray(targets)[:, None],
                               axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# ---- the comparison that decides `correct` --------------------------------

# Relative RMS error of the program's logits against the reference's,
# by the compute dtype the configuration STATES. float32: both sides do
# the same float32 arithmetic in another order, 1e-6-class error through a
# few layers; 2e-4 leaves room for depth and long rows and is 50 times
# under what bf16 anywhere in the block gives. bfloat16: activations and
# matmul inputs rounded to 8 bits of mantissa (2^-9 relative each) through
# every layer, float32 accumulation and a float32 vocabulary head; read on
# the chip at 18 layers x 2048 wide (my chip runs, PR 23): 0.026 for the
# train forward over 4,096 positions, 0.030-0.038 for prefill and decode
# through the slot cache. The bound is a little over twice the worst
# reading; an 8-bit float or integer path (2^-4 relative, sixteen times
# bf16's rounding) lands several times above it.
LOGIT_REL_RMS_TOL = {"float32": 2e-4, "bfloat16": 8e-2}
# |loss - reference loss| on the compared rows: a mean over thousands of
# positions, so rounding errors average out; bf16 reads 1e-3-class.
LOSS_ABS_TOL = {"float32": 1e-4, "bfloat16": 1e-2}


def rel_rms_error(got, want) -> float:
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.mean(want ** 2)))


def logits_agree(got, want, compute_dtype: str) -> dict:
    err = rel_rms_error(got, want)
    tol = LOGIT_REL_RMS_TOL[compute_dtype]
    finite = bool(jnp.all(jnp.isfinite(jnp.asarray(got))))
    return {"rel_rms_error": err, "tolerance": tol,
            "ok": finite and err <= tol}

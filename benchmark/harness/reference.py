"""The comparison that decides `correct`, for every architecture: the
program's logits and the terms of its objective against those of the
configuration's plain reference (`benchmark/architectures/<name>.py`,
found by `spec.load_architecture`; float32, matmuls at "highest"
precision, written from the published description of the block), the
logits by a relative RMS error, each term of the objective by its
absolute difference, both held to a tolerance that depends only on the
compute dtype the configuration STATES.
"""

from __future__ import annotations

import math

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
# |term - reference term| on the compared rows, for the next-token cross
# entropy and, unless its architecture file states a limit of its own
# (`TERM_ABS_TOL`, set from readings on the chip), for every further term
# of the objective an architecture brings (`reference_terms`): a mean over
# thousands of positions, so rounding errors average out; bf16 reads
# 1e-3-class (the cross entropy: 0.00001-0.0009 over 40 runs of the three
# train cells, my chip runs, PR 31).
LOSS_ABS_TOL = {"float32": 1e-4, "bfloat16": 1e-2}
# |total - weighted sum of the program's own terms| over max(1, |total|),
# where the config file states the weights (`objective`): the program adds
# float32 scalars, so the two differ by a few roundings of 6e-8; a weight
# that is off by a hundredth of a term's size is 1e4 times that.
TOTAL_REL_TOL = 1e-6


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


def objective_agrees(total: float, program: dict, reference: dict,
                     weights, compute_dtype: str,
                     term_tolerances=None) -> dict:
    """The program's objective against the reference's, term by term.

    ``program`` is the metrics dict of the program's `loss_fn` as plain
    floats, ``total`` the number it differentiates, ``reference`` the
    reference's value of every compared term by the name the program
    reports it under (`loss`, the next-token cross entropy, and whatever
    the architecture's `reference_terms` brings). A term the program does
    not report fails. ``term_tolerances`` is the architecture file's
    `TERM_ABS_TOL` (`{term: {dtype: limit}}`) or None: a further term's
    limit where its readings do not fit `LOSS_ABS_TOL`; the cross
    entropy's limit is `LOSS_ABS_TOL` whatever it says. ``weights`` is the
    config file's `objective`
    (`{term: weight}`) or None: where it is given, the total has to be
    the weighted sum of the program's OWN terms (a weight applied twice,
    left out or mistyped shows there, whatever the terms' values), and
    every term with a weight other than 0 has to be one the reference
    gives. The total is finite either way."""
    terms, ok = {}, math.isfinite(total)
    for name, want in reference.items():
        tol = LOSS_ABS_TOL[compute_dtype] if name == "loss" else (
            (term_tolerances or {}).get(name) or LOSS_ABS_TOL)[compute_dtype]
        got = program.get(name)
        good = got is not None and math.isfinite(got) \
            and abs(got - want) <= tol
        terms[name] = {"program": got, "reference": want,
                       "abs_diff": None if got is None else abs(got - want),
                       "tolerance": tol, "ok": good}
        ok = ok and good
    out = {"terms": terms, "total": total}
    if weights is not None:
        unknown = sorted(n for n, w in weights.items()
                         if n not in program or (w and n not in reference))
        weighted = sum(w * program[n] for n, w in weights.items()
                       if n in program)
        gap = abs(total - weighted) / max(1.0, abs(total))
        out["weighted_sum"] = {
            "weights": dict(weights), "value": weighted, "rel_diff": gap,
            "tolerance": TOTAL_REL_TOL, "unknown_terms": unknown,
            "ok": not unknown and gap <= TOTAL_REL_TOL}
        ok = ok and out["weighted_sum"]["ok"]
    out["ok"] = bool(ok)
    return out

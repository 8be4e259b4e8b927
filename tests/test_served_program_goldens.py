"""The two served programs are the PARENT's, operation for operation: at toy
widths on the CPU path (the masked contractions), the lowered text of
`prefill_slots` (2, 32) and of `decode_slots` at ``steps=4`` for InternLM2,
Solar-Open2 and Phi-4-mini-flash hashes to what commit 2582cbb (PR 46,
before the engine's mixer kinds became `generate.MIXERS`) gave (Granite
4.0-H: to what the PR that brought it, PR 49, gave). A change
that moves where Python keeps a branch emits the same operations in the
same order and passes; one that reorders, adds or drops an operation
changes what is compiled, loaded and measured, and fails here before any
chip time is spent. The hashes were made by running this file (`python
tests/test_served_program_goldens.py`) on the parent's tree; make them
again only in a PR that means to change the programs, and say so there."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402
from ray_tpu.models.engine import (decode_slots, init_slot_cache,  # noqa: E402
                                   prefill_slots)
from ray_tpu.models.transformer import init_params, serving_params  # noqa: E402

# the toy widths of test_layer_pattern_goldens.py and the two *_reference.py
TOYS = {
    "internlm2-1.8b": dict(
        attention_impl="xla", remat=False, max_seq_len=128, dtype="float32",
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128),
    "solar-open2-250b": dict(
        attention_impl="xla", remat=False, max_seq_len=128, dtype="float32",
        vocab_size=256, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=24, kda_heads=4, kda_head_dim=8, kda_gate_rank=6,
        moe_experts=16, moe_held_experts=4, moe_top_k=4, moe_shared_d_ff=24),
    "phi-4-mini-flash-reasoning": dict(
        vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=48, mamba_d_state=4, mamba_dt_rank=3, sliding_window=8,
        dtype="float32", param_dtype="float32"),
    "granite-4.0-h-micro": dict(
        vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=48, mamba_heads=8, mamba_head_dim=8, mamba_d_state=16,
        mamba_chunk=8, dtype="float32", param_dtype="float32"),
}
SLOTS, MAX_LEN, GROUP, STEPS = 4, 48, (2, 32), 4
PARENT = {
    "internlm2-1.8b": {"prefill": "02546b65d391bea0",
                       "decode": "50f3b6bda8810678"},
    "solar-open2-250b": {"prefill": "1ac05443e8346d86",
                         "decode": "8e266f221f3ad6b4"},
    "phi-4-mini-flash-reasoning": {"prefill": "2450e959c4784041",
                                   "decode": "0326e1264dd9f5db"},
}
# a configuration that came later: its programs' text on the tree of the PR
# that brought it (PR 49), held from then on as the three above are
PARENT["granite-4.0-h-micro"] = {"prefill": "2d64df4c13a69164",
                                 "decode": "1571abf10c7b8621"}


def _lower(cfg, program, slots, max_len, group, on=lambda x: x):
    """`prefill_slots` at ``group`` or `decode_slots` at ``STEPS``, lowered
    for shapes alone (``on`` places them, on a described chip)."""
    def shaped(*shape, dtype=jnp.int32):
        return on(jax.ShapeDtypeStruct(shape, dtype))

    params, cache, rng = (jax.tree.map(on, jax.eval_shape(make)) for make in (
        lambda: serving_params(init_params(jax.random.key(0), cfg), cfg),
        lambda: init_slot_cache(cfg, slots, max_len),
        lambda: jax.random.key(0)))
    if program == "prefill":
        K = group[0]
        return prefill_slots.lower(params, cache, shaped(*group), shaped(K),
                                   shaped(K), rng, cfg)
    return decode_slots.lower(params, cache, shaped(slots),
                              shaped(slots, dtype=jnp.bool_), rng, cfg,
                              steps=STEPS)


def _hash(text: str) -> str:
    assert "loc(" not in text
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def golden(name: str, program: str) -> str:
    """The hash of ``program``'s lowered text (StableHLO as `as_text` prints
    it: no locations, no metadata) for the toy of configuration ``name``."""
    cfg = spec.build_transformer_config(
        spec.load_config(spec.load_benchmark(), name), **TOYS[name])
    return _hash(_lower(cfg, program, SLOTS, MAX_LEN, GROUP).as_text())


def described_v5e() -> dict:
    """By hand, on the parent and on the change (`python
    tests/test_served_program_goldens.py --v5e`, a minute; not a test: it
    loads the TPU compiler): the same hashes at the CELLS' widths for a
    described v5e, the kernels' path, of every (K, P) program `warmup()`
    makes and the decode chunk. A Mosaic kernel's serialized body carries
    the Python call stack of its `pallas_call` (paths, functions, lines) as
    locations: it is parsed and printed without them first, else no two
    trees, and no refactor, would ever hash alike."""
    import base64
    import importlib
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import engine, moe

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    for module in ("flash_attention", "decode_attention", "kda",
                   "grouped_matmul", "mamba", "mamba2"):
        # as `test_chip_compile`
        importlib.import_module(
            "ray_tpu.ops." + module)._use_interpret = lambda: False
    engine._on_chip = moe._on_chip = lambda: True

    def body(match):
        ctx = mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            return "body:" + _hash(ir.Module.parse(base64.b64decode(
                match.group(1))).operation.get_asm(enable_debug_info=False))

    def on(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    out = {}
    for name, traffic in (("internlm2-1.8b", "batch-closed"),
                          ("solar-open2-250b", "batch-closed-128"),
                          ("phi-4-mini-flash-reasoning", "reason-closed-64"),
                          ("granite-4.0-h-micro", "reason-closed-64")):
        cfg = spec.build_transformer_config(
            spec.load_config(spec.load_benchmark(), name))
        dep = spec.load_traffic(traffic)["deployment"]
        longest = dep["max_prompt_len"]
        size = dep["slots"], longest + dep["max_new_tokens"]
        groups = [(K, min(P, longest)) for P in (16, 32, 64, 128, 256, 512,
                                                 1024) for K in (4, 2, 1)]
        for program, group in [("prefill", g) for g in groups] \
                + [("decode", None)]:
            text = _lower(cfg, program, *size, group, on).as_text()
            out[f"{name} {program} {group}"] = _hash(re.sub(
                r'\\22body\\22: \\22(.*?)\\22', body, text))
    out["all"] = _hash(json.dumps(out, sort_keys=True))
    return out


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("name", sorted(TOYS))
def test_a_served_program_is_the_parents_operation_for_operation(name,
                                                                 program):
    assert jax.default_backend() == "cpu"
    assert golden(name, program) == PARENT[name][program]


if __name__ == "__main__":
    print(json.dumps(described_v5e() if "--v5e" in sys.argv else {
        name: {program: golden(name, program)
               for program in ("prefill", "decode")}
        for name in sorted(TOYS)}, indent=1))

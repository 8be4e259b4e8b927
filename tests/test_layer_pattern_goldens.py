"""The six accepted configurations after the layer-pattern change: at toy
widths, the tree `init_params` makes (paths, shapes, dtypes, and the leaves'
sums), the loss of a seeded batch and the tokens the engine serves are what
the PARENT commit (73d4318, before `TransformerConfig.layer_pattern`) gave.
One segment is the period it always was: same stacks, same keys, same
programs. The numbers were made by running this file's `golden` on the
parent's tree."""

import hashlib
import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.engine import InferenceEngine  # noqa: E402

TOYS = {
    "internlm2-1.8b": dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                           n_kv_heads=2, d_ff=128),
    "mistral-7b-v0.3": dict(vocab_size=384, d_model=48, n_layers=3,
                            n_heads=4, n_kv_heads=2, d_ff=96),
    "olmoe-1b-7b": dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                        n_kv_heads=4, d_ff=32, moe_experts=8, moe_top_k=2),
    "glm-4.7-flash": dict(
        vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=24, head_dim=12, v_head_dim=16, rope_head_dim=4, q_lora_rank=10,
        kv_lora_rank=8, moe_experts=16, moe_held_experts=4,
        moe_first_expert=4, moe_top_k=2, moe_shared_d_ff=24,
        moe_dense_d_ff=40),
    "kimi-linear-48b-a3b": dict(
        vocab_size=96, d_model=32, n_layers=5, n_heads=4, n_kv_heads=4,
        d_ff=24, nope_head_dim=8, rope_head_dim=4, v_head_dim=8,
        kv_lora_rank=8, kda_heads=4, kda_head_dim=8, kda_gate_rank=6,
        moe_experts=32, moe_held_experts=8, moe_first_expert=8, moe_top_k=8,
        moe_shared_d_ff=24, moe_dense_d_ff=40),
    "solar-open2-250b": dict(
        vocab_size=256, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=24, kda_heads=4, kda_head_dim=8, kda_gate_rank=6,
        moe_experts=16, moe_held_experts=4, moe_top_k=4, moe_shared_d_ff=24),
}
# the parent's: `served` None where it refuses to serve the configuration
PARENT = json.loads(r"""{"glm-4.7-flash": {"leaves": 52, "loss": 5.4399824142456055, "served": null,
"sums": [415.89142163904137, 8186.721040476568], "tree":
"23da285d27816ec1"}, "internlm2-1.8b": {"leaves": 12, "loss":
6.796106338500977, "served": [[339, 479, 370, 4, 26, 378, 136, 3], [118,
415, 461, 299, 217, 106, 284, 329]], "sums": [329.6927766674236,
13351.615126562228], "tree": "d1ba683a239d1adf"}, "kimi-linear-48b-a3b":
{"leaves": 113, "loss": 5.00917911529541, "served": null, "sums":
[-197.63608979977096, 15535.07352728787], "tree": "0f5227d69ee9a4d1"},
"mistral-7b-v0.3": {"leaves": 12, "loss": 6.386241912841797, "served": [[27,
19, 19, 19, 19, 19, 237, 249], [275, 22, 380, 19, 19, 19, 19, 19]], "sums":
[335.52084252674604, 10616.543971072693], "tree": "c7cb255de907431a"},
"olmoe-1b-7b": {"leaves": 15, "loss": 6.779482841491699, "served": [[435,
414, 454, 111, 12, 341, 112, 319], [214, 122, 102, 103, 444, 438, 8, 414]],
"sums": [634.2396356990853, 17718.710841276614], "tree":
"8a7a00e30fc5839c"}, "solar-open2-250b": {"leaves": 93, "loss":
6.045345306396484, "served": [[201, 13, 69, 41, 26, 45, 32, 182], [219, 36,
199, 28, 245, 238, 138, 112]], "sums": [-123.89854650199413,
10679.546878919005], "tree": "540275e32f5178b5"}}""")


def golden(name: str) -> dict:
    bench = spec.load_benchmark()
    conf = spec.load_config(bench, name)
    cfg = spec.build_transformer_config(
        conf, attention_impl="xla", remat=False, max_seq_len=128,
        dtype="float32", **TOYS[name])
    params = transformer.init_params(jax.random.key(5), cfg)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    tree = hashlib.sha256(json.dumps(
        [[jax.tree_util.keystr(p), list(x.shape), x.dtype.name]
         for p, x in leaves]).encode()).hexdigest()[:16]
    sums = [float(sum(np.asarray(x, np.float64).sum() for _, x in leaves)),
            float(sum(np.abs(np.asarray(x, np.float64)).sum()
                      for _, x in leaves))]
    toks = jax.random.randint(jax.random.key(6), (2, 33), 0, cfg.vocab_size)
    loss, _ = jax.jit(lambda p, t: transformer.loss_fn(
        p, {"tokens": t}, cfg))(params, toks)
    out = {"tree": tree, "sums": sums, "leaves": len(leaves),
           "loss": float(loss)}
    try:
        eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                              max_new_tokens=8)
        out["served"] = [eng.generate(list(range(3, 3 + n)), 8)
                         for n in (5, 11)]
    except NotImplementedError:
        out["served"] = None
    return out


@pytest.mark.parametrize("name", sorted(TOYS))
def test_an_accepted_configuration_builds_what_the_parent_built(name):
    got, want = golden(name), PARENT[name]
    assert (got["tree"], got["leaves"]) == (want["tree"], want["leaves"])
    np.testing.assert_allclose(got["sums"], want["sums"], rtol=1e-9)
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-6)
    assert got["served"] == want["served"]
    # every accepted configuration is ONE segment
    cfg = spec.build_transformer_config(
        spec.load_config(spec.load_benchmark(), name), **TOYS[name])
    assert cfg.layer_pattern is None
    assert len(cfg.segments(cfg.moe_dense_layers)) == 1

"""The main path's programs, compiled for a described v5e at real widths.

No chip is attached here: the TPU compiler that ships with libtpu
compiles for a *described* `v5e:2x2` topology, which refuses what the
chip would refuse (a kernel that cannot be partitioned, a VMEM overrun, a
program over 16 GB) at no chip time. Nothing runs, so nothing here says
anything about results or speed — chip_smoke.py does that on the chip.

Under JAX_PLATFORMS=cpu the model code would pick the Pallas interpreter
(`_use_interpret`) or no kernel at all (`attention_impl="auto"` -> xla);
the fixtures steer both so the compiled text really holds the Mosaic
kernel (`tpu_custom_call`).
"""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models.config import llama3_1b_config
from ray_tpu.models.training import (batch_sharding, make_init_fn,
                                     make_optimizer, make_train_step,
                                     state_shardings)

HBM_BYTES = 16 * 1024 ** 3  # one v5e chip
BATCH, SEQ = 4, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the Mosaic kernels, not the interpreter the CPU backend picks,
    and let decode pick its kernel by the cache's shape as it does on a
    chip (under the CPU backend it takes the masked contraction)."""
    from ray_tpu.models import engine, moe

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    da = importlib.import_module("ray_tpu.ops.decode_attention")
    kda = importlib.import_module("ray_tpu.ops.kda")
    gm = importlib.import_module("ray_tpu.ops.grouped_matmul")
    mamba = importlib.import_module("ray_tpu.ops.mamba")
    for module in (fa, da, kda, gm, mamba):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    for module in (engine, moe):
        monkeypatch.setattr(module, "_on_chip", lambda: True)
    engine.decode_slots.clear_cache()
    yield
    engine.decode_slots.clear_cache()


def _llama(**kw):
    return llama3_1b_config(max_seq_len=SEQ, param_dtype=jnp.bfloat16,
                            attention_impl="pallas", **kw)


def _on(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding`` (one sharding
    or a matching pytree of them)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes


def _flash_loss(q, k, v, **kw):
    from ray_tpu.ops import flash_attention

    return flash_attention(q, k, v, **kw).astype(jnp.float32).sum()


@pytest.mark.parametrize("shape, kw", [
    ((BATCH, SEQ, 32, 64), {}),  # llama3-1b, chip_smoke.py
    ((4, 4096, 16, 128), {}),    # a chip's share in all three train cells
    # latent attention expanded: 8 rows x 20 heads, q, k and v 256 wide
    ((8, 4096, 20, 256), {}),
    ((1, 8192, 4, 128), {}),     # K/V whole and in float32 overran VMEM here
    ((1, 16384, 2, 128), {}),
    # lengths that are no multiple of a tile: the block is, and T is padded
    ((2, 197, 4, 64), dict(causal=False)),  # an encoder (ViT's 196 + 1)
    ((2, 300, 4, 64), {}),
    ((2, 77, 4, 64), {}),
    ((2, 700, 4, 128), {}),  # one block of 768, the largest that is picked
    ((1, 1000, 4, 128), dict(causal=False)),
    ((2, 300, 4, 64), dict(block_q=100, block_k=40)),  # named, not whole tiles
], ids=lambda x: f"T{x[1]}x{x[3]}" if isinstance(x, tuple) else
   "-".join(f"{k}{v}" for k, v in x.items()) or "causal")
def test_flash_forward_and_backward_compile(one_chip, mosaic, shape, kw):
    from ray_tpu.ops import flash_attention

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    fwd = jax.jit(functools.partial(flash_attention, **kw)).lower(
        x, x, x).compile()
    assert "tpu_custom_call" in fwd.as_text()
    bwd = jax.jit(jax.grad(functools.partial(_flash_loss, **kw),
                           argnums=(0, 1, 2))).lower(x, x, x).compile()
    # the forward kernel and the backward kernel
    assert bwd.as_text().count("tpu_custom_call") >= 2


def _kernel_float32_converts(jaxpr, inside=False) -> list:
    """Shapes of every `convert_element_type -> float32` inside the
    Pallas kernels of ``jaxpr`` (their loops and branches included)."""
    found = []
    for eqn in jaxpr.eqns:
        kernel = inside or eqn.primitive.name == "pallas_call"
        if (inside and eqn.primitive.name == "convert_element_type"
                and eqn.params["new_dtype"] == jnp.float32):
            found.append(tuple(eqn.outvars[0].aval.shape))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    found += _kernel_float32_converts(sub, kernel)
    return found


def test_flash_operands_reach_the_mxu_as_they_arrive(mosaic):
    """No kernel converts an operand to float32 at K/V's whole [T, d]
    (the parent's kernels did, three operands each: VMEM and VPU time the
    MXU then rounded away); what float32 there is, is tile-sized."""
    T = 4096
    x = jax.ShapeDtypeStruct((4, T, 16, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(_flash_loss, argnums=(0, 1, 2)))(x, x, x)
    assert str(jaxpr).count("pallas_call") >= 2
    assert not [s for s in _kernel_float32_converts(jaxpr.jaxpr) if T in s]


def test_llama3_1b_train_step_fits_one_chip(one_chip, mosaic):
    cfg = _llama()
    tx = make_optimizer(3e-4, mu_dtype=jnp.bfloat16)
    state = _on(jax.eval_shape(make_init_fn(cfg, tx), jax.random.key(0)),
                one_chip)
    batch = {"tokens": jax.ShapeDtypeStruct((BATCH, SEQ + 1), jnp.int32,
                                            sharding=one_chip)}
    compiled = make_train_step(cfg, tx).lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def _internlm2():
    from benchmark.harness import spec

    return spec.build_transformer_config(
        spec.load_config(spec.load_benchmark(), "internlm2-1.8b"))


@pytest.mark.parametrize("make_cfg, slots, max_len, group, kernel", [
    # chip_smoke's sizes: 8 slots, prompts to 512, 64 new tokens (max_len
    # 640 covers the issue's rehearsal size). A head of 64 is stored
    # padded to 128 lanes, which the decode kernel's DMA cannot slice:
    # this preset keeps the masked contraction
    (llama3_1b_config, 8, 640, (8, 512), False),
    # the serve cells' own size, InternLM2's widths (heads of 128)
    (_internlm2, 32, 1280, (4, 1024), True),
], ids=["llama3-1b-8x640", "internlm2-1.8b-32x1280"])
def test_serve_programs_compile(one_chip, mosaic, make_cfg, slots, max_len,
                                group, kernel):
    """The serve replica's programs, decode chunks of 4, and the weights
    as the engine holds them (`serving_params`: the preset's compute
    dtype, the vocabulary head float32 with its bf16 copy beside it)."""
    from ray_tpu.models.engine import (decode_slots, init_slot_cache,
                                       prefill_slots)
    from ray_tpu.models.transformer import (HEAD_COPY, init_params,
                                            serving_params)

    cfg = make_cfg()
    K, P = group
    params = _on(jax.eval_shape(
        lambda k: serving_params(init_params(k, cfg), cfg),
        jax.random.key(0)), one_chip)
    cache = _on(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)), one_chip)
    rng = _on(jax.eval_shape(lambda: jax.random.key(0)), one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    prefill = prefill_slots.lower(params, cache, i32(K, P), i32(K),
                                  i32(K), rng, cfg).compile()
    assert _device_bytes(prefill) < HBM_BYTES
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    decode = decode_slots.lower(params, cache, i32(slots), active, rng, cfg,
                                steps=4).compile()
    assert _device_bytes(decode) < HBM_BYTES
    # The programs read the weights as they are held: no instruction
    # converts a weight (a tree held in another dtype than the forward
    # reads brings back one convert of every matrix per PROGRAM: a third
    # of a decode chunk on the chip, 13.8 ms of every prefill), the
    # float32 vocabulary head among them: `lm_head` multiplies by the bf16
    # copy the tree holds (`with_head_copy`), the MXU's operand, which XLA
    # made of the float32 leaf once a program before (525 MB of
    # temporaries and 1.8 ms of every decode chunk of InternLM2's), and no
    # temporary is weight-sized (the smallest stacked matrix is 33 MB):
    # decode's are small change, the prefill's its own activations, the
    # float32 scores and probabilities of [K x P] rows first (268 MB each
    # at 8 x 512).
    head = params["embed" if cfg.tie_embeddings else "lm_head"]
    assert head.dtype == jnp.float32
    assert params[HEAD_COPY].dtype == jnp.bfloat16
    assert params[HEAD_COPY].shape == head.shape
    cache_bytes = 2 * cache["k"].size * cache["k"].dtype.itemsize
    scores = K * cfg.n_heads * P * P * 4
    # The prompt pass is given the tree without the copy and rounds the
    # leaf itself, once a program (`prefill_slots` says why).
    head_copy = head.size * 2
    for program, room, but in ((decode, 16 << 20, ()),
                               (prefill, head_copy + 3 * scores,
                                head.shape)):
        assert _weight_converts(program.as_text(), params, but=but) == []
        assert program.memory_analysis().temp_size_in_bytes \
            < 0.1 * cache_bytes + room
    assert _head_sized_ops(decode.as_text(), head) == []
    # A decode substep writes only the rows that change, in place: no
    # instruction copies, selects over or scatters into a whole-cache-sized
    # result (a per-layer write inside the layer scan, or a cache stored in
    # another order than attention reads it, brings exactly those back:
    # 2/3 of decode time on the chip)
    text = decode.as_text()
    assert _whole_cache_ops(text, cache["k"].shape) == []
    # and, where the kernel takes the cache, reads it through the kernel
    # alone, which picks its layer itself: the one Mosaic call of the
    # program is the named kernel, and nothing materialises a layer's
    # [slots, KV, S, hd] slab of K or V on the way in, as a float32 copy
    # (the contraction's) or as a slice cut out for the custom call (84 MB
    # a layer, K and V each, at the cells' size)
    assert ("tpu_custom_call" in text) == kernel
    if kernel:      # a GQA layer's call hands o back in q's dtype
        assert _decode_attention_results(text) == [
            f"bf16[{slots},{cfg.kv_heads},{cfg.n_heads // cfg.kv_heads},"
            f"{cfg.head_dim}]"]
        assert _layer_slab_ops(text, cache["k"].shape[1:]) == []


def _decode_attention_results(hlo: str) -> list:
    """["dtype[dims]"] of the program's `decode_attention` custom calls."""
    return re.findall(r"%decode_attention\S* = (\w+\[[\d,]+\])\S* "
                      r"custom-call\(", hlo)


def _layer_slab_ops(hlo: str, slab_shape) -> list:
    """`name = type[...] convert|copy|dynamic-slice|fusion(...)` lines of
    compiled text whose result has one layer's cache dimensions (in any
    order, 1s dropped)."""
    want = sorted(d for d in slab_shape if d > 1)
    found = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* "
            r"(convert|copy|dynamic-slice|fusion)\(", hlo, re.M):
        dims = sorted(int(d) for d in m.group(2).split(",") if int(d) > 1)
        if dims == want:
            found.append(f"{m.group(1)}: {m.group(3)}")
    return found


def _weight_converts(hlo: str, params, but=()) -> list:
    """`name = f32|bf16[...] convert(...)` lines of compiled text whose
    result has the dimensions of a weight matrix: a whole leaf, or one
    layer of a stacked one (in any order, 1s dropped). ``but``: the one
    shape let through."""
    shapes = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        dims = tuple(sorted(d for d in leaf.shape if d > 1))
        shapes.add(dims)
        if path[0].key == "layers":
            shapes.add(tuple(sorted(d for d in leaf.shape[1:] if d > 1)))
    shapes = {s for s in shapes if len(s) >= 2}  # matrices, not gains
    shapes.discard(tuple(sorted(but)))
    found = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* convert\(", hlo, re.M):
        dims = tuple(sorted(int(d) for d in m.group(2).split(",")
                            if int(d) > 1))
        if dims in shapes:
            found.append(f"{m.group(1)}: [{m.group(2)}]")
    return found


def _head_sized_ops(hlo: str, head) -> list:
    """`name = type[...] convert|copy|transpose(...)` lines of compiled
    text whose result has as many elements as the vocabulary head: the
    head made again in another dtype or layout, once a program (inside a
    decode chunk: hoisted out of its substeps and no further)."""
    found = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?(\S+) = (\w+)\[([\d,]+)\]\S* "
            r"(convert|copy|transpose)\(", hlo, re.M):
        if np.prod([int(d) for d in m.group(3).split(",")]) == head.size:
            found.append(f"{m.group(1)}: {m.group(2)}[{m.group(3)}] "
                         f"{m.group(4)}")
    return found


def _whole_cache_ops(hlo: str, cache_shape) -> list:
    """`name = bf16[...] copy|select|scatter(...)` lines of compiled text
    whose result has the cache's dimensions, in any order."""
    found = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* "
            r"(copy|select|scatter)\(", hlo, re.M):
        dims = sorted(int(d) for d in m.group(2).split(","))
        if dims == sorted(cache_shape):
            found.append(f"{m.group(1)}: {m.group(3)}")
    return found


def test_tensor2_decode_runs_the_kernel_per_shard(topo, mosaic):
    """The serve cells' decode chunk on a `tensor=2` mesh of described
    chips: GSPMD cannot partition a Mosaic kernel, so `_kernel_attention`
    runs it per shard of the KV heads; each chip's call sees 4 of the 8."""
    from ray_tpu.models.engine import (cache_logical_axes, decode_slots,
                                       init_slot_cache)
    from ray_tpu.models.transformer import (HEAD_COPY, init_params,
                                            param_logical_axes,
                                            serving_params)
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.parallel.sharding import logical_sharding, tree_shardings

    cfg = _internlm2()
    slots = 32
    mesh = MeshSpec(data=1, fsdp=1, tensor=2).build(topo.devices[:2])
    shardings = tree_shardings(mesh, param_logical_axes(cfg))
    shardings[HEAD_COPY] = shardings["lm_head"]     # as `serving_params`
    params = _on(jax.eval_shape(
        lambda k: serving_params(init_params(k, cfg), cfg),
        jax.random.key(0)), shardings)
    axes = cache_logical_axes()
    cache = _on(jax.eval_shape(lambda: init_slot_cache(cfg, slots, 1280)),
                {k: logical_sharding(mesh, axes[k]) for k in axes})
    whole = logical_sharding(mesh, (None,))
    rng = _on(jax.eval_shape(lambda: jax.random.key(0)),
              logical_sharding(mesh, ()))
    tokens = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=whole)
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=whole)
    text = decode_slots.lower(params, cache, tokens, active, rng, cfg,
                              steps=4, mesh=mesh).compile().as_text()
    assert _decode_attention_results(text) == [
        f"bf16[{slots},{cfg.kv_heads // 2},"
        f"{cfg.n_heads // cfg.kv_heads},{cfg.head_dim}]"]
    assert "all-reduce" in text  # the tensor-parallel output projection
    # each chip multiplies by its half of the head's copy as it lies
    assert _head_sized_ops(text, params["lm_head"]) == []
    half = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab_size // 2),
                                jnp.bfloat16)
    assert _head_sized_ops(text, half) == []
    assert _whole_cache_ops(text, (cfg.n_layers, slots, cfg.kv_heads // 2,
                                   1280, cfg.head_dim)) == []


def test_fsdp2_tensor2_train_step_keeps_kernel(topo, mosaic):
    """The sharded step on four described chips: GSPMD cannot partition a
    Mosaic kernel, so `_attention` must run it per shard (shard_map) —
    bare, this compile raises NotImplementedError."""
    from ray_tpu.parallel import MeshSpec

    cfg = _llama()
    tx = make_optimizer(3e-4, mu_dtype=jnp.bfloat16)
    mesh = MeshSpec(fsdp=2, tensor=2).build(topo.devices)
    state = _on(jax.eval_shape(make_init_fn(cfg, tx), jax.random.key(0)),
                state_shardings(cfg, tx, mesh))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (BATCH, SEQ + 1), jnp.int32, sharding=batch_sharding(mesh))}
    compiled = make_train_step(cfg, tx, mesh).lower(state, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text and "reduce-scatter" in text
    # ZeRO-3 x TP: each chip holds about a quarter of the train state
    assert _device_bytes(compiled) < HBM_BYTES // 2


def _computations(text) -> dict:
    """{name: [instruction lines]} of a compiled program's text."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _grouped_matmul_conditionals(text) -> list:
    """[(whole, front)]: for every conditional of the program whose
    branches hold a grouped matmul, the instruction lines of its false
    and of its true branch (`lax.cond(live <= front, ...)` in
    models/moe.py: true is the front of the sorted buffer), each with the
    computations it calls."""
    comps = _computations(text)

    def lines(root):
        seen, todo = [], [root]
        while todo:
            c = todo.pop()
            if c in seen or c not in comps:
                continue
            seen.append(c)
            for line in comps[c]:
                for m in re.finditer(
                        r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                        line):
                    todo.append(m.group(1))
        return [line for c in seen for line in comps[c]]
    found = []
    for body in comps.values():
        for line in body:
            m = re.search(r" conditional\(.*branch_computations=\{([^}]*)\}",
                          line)
            if m is None:
                continue
            false, true = (lines(b.strip().lstrip("%"))
                           for b in m.group(1).split(","))
            if any("ragged-dot" in x for x in false + true):
                found.append((false, true))
    return found


def _shaped(lines, *dims) -> list:
    """The instruction lines that name an array of exactly ``dims``."""
    tag = "[" + ",".join(str(d) for d in dims) + "]"
    return [x.strip() for x in lines if tag in x]


def _front_holds_no_whole_buffer(text, assignments, front, d, f, at_least):
    """A step whose chip holds a share of the experts: each expert stage
    is one conditional forward and one backward; the front's branch
    multiplies ``front`` rows and holds no [N*k, f] array at all, and of
    [N*k, d] only the two gathers that are N*k INDICES long (the combine's
    forward, the dispatch's backward: each reads a front-sized source) with
    the sum over the k slots that consumes each."""
    conds = _grouped_matmul_conditionals(text)
    assert len(conds) >= at_least, len(conds)
    for whole, compact in conds:
        assert _shaped(whole, assignments, f)
        assert _shaped(compact, front, d) and _shaped(compact, front, f)
        assert not _shaped(compact, assignments, f)
        wide = _shaped(compact, assignments, d)
        assert 0 < len(wide) <= 8 < len(_shaped(whole, assignments, d)), wide
        assert all(re.search(r"moe\.(combine|dispatch)", x) for x in wide
                   if "op_name=" in x), wide
    for scope in ("moe.dispatch", "moe.experts", "moe.combine"):
        assert scope in text, scope


def _keeps_ragged_dot(monkeypatch, lower, text):
    """A program whose row buffers are all longer than one row tile:
    its compiled ``text`` holds no `ragged-dot-rows` kernel, and what
    ``lower()`` (a fresh trace each call) hands the compiler is, to the
    letter, what it hands it with that kernel ruled out: the parent's
    program, so the parent's compiled text and operation histogram."""
    from ray_tpu.models import moe

    assert "ragged-dot-rows" not in text
    lowered = []
    for ruled_out in (False, True):     # one call site: a kernel's
        with monkeypatch.context() as m:    # payload carries the stack
            if ruled_out:
                m.setattr(moe, "_rows_kernel", lambda *a: False)
            lowered.append(lower().as_text())
    assert "ragged_dot" in lowered[0] and lowered[0] == lowered[1]


def test_olmoe_train_step_fits_one_chip_without_a_capacity_tensor(
        one_chip, mosaic, monkeypatch):
    """The cell `olmoe-1b-7b.train-4k` as the benchmark runs it (its
    config file's depth, 4 x 4096, bf16 weights and moments): the step
    compiles for one chip (the compiler refuses a program over the chip's
    memory, so a compile that returns fits), its expert matmuls are the
    grouped `ragged-dot` kernels, and no buffer has the [.., E, C] shape
    of a capacity-bound dispatch or anything near its size."""
    from benchmark.harness import spec

    bench = spec.load_benchmark()
    conf = spec.load_config(bench, "olmoe-1b-7b")
    traffic = spec.load_traffic("train-4k")
    rows, seq = traffic["rows"], traffic["seq_len"]
    cfg = spec.build_transformer_config(
        conf, max_seq_len=seq, param_dtype=traffic["param_dtype"],
        attention_impl="pallas")
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.d_ff) == (64, 8, 1024)
    assert cfg.qk_norm and not cfg.moe_norm_topk
    tx = make_optimizer(traffic["learning_rate"],
                        mu_dtype=jnp.dtype(traffic["mu_dtype"]))
    state = _on(jax.eval_shape(make_init_fn(cfg, tx), jax.random.key(0)),
                one_chip)
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32,
                                            sharding=one_chip)}
    text = make_train_step(cfg, tx).lower(state, batch).compile().as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    _keeps_ragged_dot(monkeypatch,
                      lambda: make_train_step(cfg, tx).lower(state, batch),
                      text)
    # every expert is held: no front, no conditional around the experts
    assert not _grouped_matmul_conditionals(text)
    E, k = cfg.moe_experts, cfg.moe_top_k
    capacity = -(-seq * k // E) * 5 // 4          # the old C at factor 1.25
    largest = rows * seq * cfg.vocab_size         # the float32 logits
    for m in re.finditer(r"\b\w+\[([\d,]+)\]", text):
        dims = [int(d) for d in m.group(1).split(",")]
        assert (E, capacity) not in zip(dims, dims[1:]), m.group(0)
        n = 1
        for d in dims:
            n *= d
        assert n <= largest, m.group(0)


def test_glm_train_step_fits_one_chip_at_the_depth_its_file_states(
        one_chip, mosaic, monkeypatch):
    """The cell `glm-4.7-flash.train-4k-8rows` as the benchmark runs it
    (its config file's depth, 8 x 4096, bf16 weights and moments): the
    step compiles for one chip (a compile that returns fits), holds the
    attention kernel and the grouped `ragged-dot` kernels, its expert
    weights are the 8 HELD experts' and its router is 64 wide, and no
    buffer is larger than the float32 logits over the vocabulary slice."""
    from benchmark.harness import spec

    bench = spec.load_benchmark()
    conf = spec.load_config(bench, "glm-4.7-flash")
    traffic = spec.load_traffic("train-4k-8rows")
    rows, seq = traffic["rows"], traffic["seq_len"]
    cfg = spec.build_transformer_config(
        conf, max_seq_len=seq, param_dtype=traffic["param_dtype"],
        attention_impl="pallas")
    assert cfg.n_layers == conf["num_hidden_layers"] >= 5
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_top_k) == (64, 8, 4)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rope_head_dim) == (256, 256,
                                                                 64)
    tx = make_optimizer(traffic["learning_rate"],
                        mu_dtype=jnp.dtype(traffic["mu_dtype"]))
    shapes = jax.eval_shape(make_init_fn(cfg, tx), jax.random.key(0))
    lay = shapes["params"]["layers"]
    assert lay["w_gate"].shape == (cfg.n_layers - 1, 8, 2048, 1536)
    assert lay["router"].shape == (cfg.n_layers - 1, 2048, 64)
    assert shapes["params"]["dense_layers"]["w_gate"].shape == (1, 2048,
                                                                10240)
    assert shapes["params"]["embed"].shape == (19360, 2048)
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32,
                                            sharding=one_chip)}
    text = make_train_step(cfg, tx).lower(
        _on(shapes, one_chip), batch).compile().as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    _keeps_ragged_dot(monkeypatch, lambda: make_train_step(cfg, tx).lower(
        _on(shapes, one_chip), batch), text)
    for scope in ("mla.q", "mla.kv", "mla.rope", "mla.out", "moe.shared",
                  "mtp.merge", "mtp.block", "mtp.head"):
        assert scope in text, scope
    # the layer stack's and the prediction module's, forward and backward
    _front_holds_no_whole_buffer(text, rows * seq * 4, 32768, 2048, 1536,
                                 at_least=4)
    largest = rows * seq * cfg.vocab_size         # the float32 logits
    for m in re.finditer(r"\b\w+\[([\d,]+)\]", text):
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        assert n <= largest, m.group(0)


def test_flash_compiles_at_the_longest_row_a_192_wide_head_holds(one_chip,
                                                                 mosaic):
    """Latent attention without positions as the Kimi-Linear cell calls
    the kernel: 2 rows x 16,384 x 32 heads, query and key 192 wide (256
    lanes) and the 128-wide value zero-padded to 192 outside the kernel:
    the longest whole row the kernel's resident side holds on a v5e."""
    from ray_tpu.ops import flash_attention

    x = jax.ShapeDtypeStruct((2, 16384, 32, 192), jnp.bfloat16,
                             sharding=one_chip)
    bwd = jax.jit(jax.grad(functools.partial(_flash_loss, scale=192 ** -0.5),
                           argnums=(0, 1, 2))).lower(x, x, x).compile()
    assert bwd.as_text().count("tpu_custom_call") >= 2


def _kernels(text):
    """[(xplane.op_key, op_name)] of the Mosaic kernels in a compiled
    program's text: what a trace names each by, and the scopes it was
    traced under."""
    from benchmark.harness import xplane

    scopes = xplane.op_scopes(text)
    return [(key, scopes[key]) for key in scopes
            if key.startswith(f"{xplane.KERNEL_TAG}:")]


def test_kda_scan_compiles_forward_and_backward_at_the_cells_shape(one_chip,
                                                                   mosaic):
    """The chunked gated delta rule over 2 rows x 16,384 x 32 heads of 128
    (bf16 q, k, v; float32 decays and beta), forward and backward, for one
    chip: two Mosaic kernels whose names begin `kda` under the scope
    `kda.scan` (what `kda_scan_roofline` and the attention metrics'
    pattern find them by), no loop of XLA's, no triangular-solve call, no
    transpose of an operand (the head is a column block) and no more than
    one relayout of each of q, k, v, g and their gradients, and what is
    held between the two stays small beside the chip."""
    from ray_tpu.ops.kda import kda_scan

    B, T, H, d = 2, 16384, 32, 128

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (s((B, T, H, d), jnp.bfloat16),) * 3 + (
        s((B, T, H, d), jnp.float32), s((B, T, H), jnp.float32))
    compiled = jax.jit(jax.grad(
        lambda *a: kda_scan(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    kernels = _kernels(text)
    assert len(kernels) >= 2
    for key, scope in kernels:
        assert key.startswith("tpu_custom_call:kda") and "kda.scan" in scope
    assert " while(" not in text and "triangular" not in text.lower()
    # nothing of an operand's size is transposed (beta [B, T, H] alone may
    # be). What XLA does move at that size, around the kernels: `[B, T, H,
    # d]` and `[B, T, H x d]` are other bytes in the chip's tiled layouts,
    # so q, k, v, g are laid out anew on the way in (`reshape`) and dq, dk,
    # dv, dg on the way out (`copy`), one operand each and no more
    operand = B * T * H * d
    moved = []
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(", text):
        n = 1
        for size in m.group(1).split(","):
            n *= int(size)
        if n >= operand and m.group(2) not in (
                "parameter", "bitcast", "get-tuple-element", "custom-call",
                "broadcast"):       # the last: this loss's constant `do`
            moved.append((m.group(2), n))
    assert len(moved) <= 8, moved
    assert all(op in ("copy", "reshape") and n == operand for op, n in moved)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 ** 3


def test_kimi_linear_train_step_fits_one_chip_at_the_depth_its_file_states(
        one_chip, mosaic, monkeypatch):
    """The cell `kimi-linear-48b-a3b.train-16k-2rows` as the benchmark
    runs it (its config file's depth, 2 x 16,384, bf16 weights and
    moments): the step compiles for one chip (a compile that returns
    fits), holds the attention kernel, the grouped `ragged-dot` kernels and
    the scan under its scopes, its expert weights are the 8 HELD experts'
    and its router is 256 wide, the expert stack is one stack a position
    of the period, and no buffer is larger than the float32 logits over
    the vocabulary slice. (One period deeper the compiler refuses it:
    `benchmark/rehearse.py --layers`, quoted in the config file; that
    compile takes minutes and is no test.)"""
    from benchmark.harness import spec

    bench = spec.load_benchmark()
    conf = spec.load_config(bench, "kimi-linear-48b-a3b")
    traffic = spec.load_traffic("train-16k-2rows")
    rows, seq = traffic["rows"], traffic["seq_len"]
    cfg = spec.build_transformer_config(
        conf, max_seq_len=seq, param_dtype=traffic["param_dtype"],
        attention_impl="pallas")
    periods = (cfg.n_layers - 1) // 4
    assert cfg.n_layers == conf["num_hidden_layers"] == 1 + 4 * periods >= 5
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_top_k) == (256, 8, 8)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rope_head_dim) == (192, 128,
                                                                 64)
    tx = make_optimizer(traffic["learning_rate"],
                        mu_dtype=jnp.dtype(traffic["mu_dtype"]))
    shapes = jax.eval_shape(make_init_fn(cfg, tx), jax.random.key(0))
    lay = shapes["params"]["layers"]
    assert ["kda_wq" in x for x in lay] == [True, True, False, True]
    assert lay[0]["w_gate"].shape == (periods, 8, 2304, 1024)
    assert lay[2]["router"].shape == (periods, 2304, 256)
    assert lay[2]["wq"].shape == (periods, 2304, 32, 192)
    assert lay[0]["kda_f_b"].shape == (periods, 128, 32, 128)
    dense = shapes["params"]["dense_layers"]
    assert dense["w_gate"].shape == (1, 2304, 9216) and "kda_wq" in dense
    assert shapes["params"]["embed"].shape == (20480, 2304)
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32,
                                            sharding=one_chip)}
    text = make_train_step(cfg, tx).lower(
        _on(shapes, one_chip), batch).compile().as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    _keeps_ragged_dot(monkeypatch, lambda: make_train_step(cfg, tx).lower(
        _on(shapes, one_chip), batch), text)
    for scope in ("kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.out",
                  "mla.q", "mla.kv", "mla.out", "moe.shared"):
        assert scope in text, scope
    # one a position of the period, forward and backward
    _front_holds_no_whole_buffer(text, rows * seq * 8, 16384, 2304, 1024,
                                 at_least=8)
    # the scan's kernels and no others are named `kda*` and lie under
    # `kda.scan`: `nope_mla_attention_step_share` counts every Mosaic
    # kernel whose name does not begin `ragged-dot` or `kda`
    kernels = _kernels(text)
    named = {key for key, _ in kernels
             if key.startswith("tpu_custom_call:kda")}
    scoped = {key for key, scope in kernels if "kda.scan" in scope}
    assert named == scoped and len(named) >= 3      # forward, remat, backward
    assert len(kernels) > len(named)                # the attention kernels
    largest = rows * seq * cfg.vocab_size         # the float32 logits
    for m in re.finditer(r"\b\w+\[([\d,]+)\]", text):
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        assert n <= largest, m.group(0)


def _sized_ops(text: str, shape, ops: str) -> list:
    """`name = type[...] <op>(...)` lines of compiled text whose result has
    ``shape``'s dimensions (in any order, 1s dropped), for the ops of the
    alternation ``ops``."""
    want = sorted(d for d in shape if d > 1)
    found = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* (" + ops + r")\(",
            text, re.M):
        if sorted(int(d) for d in m.group(2).split(",")
                  if int(d) > 1) == want:
            found.append(f"{m.group(1)}: {m.group(3)}")
    return found


def test_solar_open2_serve_programs_compile_and_move_no_state(one_chip,
                                                              mosaic,
                                                              monkeypatch):
    """The cell `solar-open2-250b.batch-closed-128` as the benchmark runs
    it (its config file's depth and share, 128 slots x 1,280 positions,
    decode chunks of 4, a prefill group of 4 x 1,024): both served
    programs compile for one chip (a compile that returns fits) with the
    weights as the engine holds them. Decode runs the two named kernels
    and the grouped matmuls (on the 512-row front the short row buffer's
    kernel, three a layer, fed the weights where they lie; `ragged_dot` on
    the whole buffer the front overflows into), updates the 1.6 GB of KDA
    states through `kda_decode_step` where they lie (aliased through the kernel and the
    two loops: nothing copies, converts, selects over or scatters into a
    whole-state-sized result, and nothing materialises one layer's [slots,
    64, 128, 128] slab on the way in or out), reads keys and values
    through `decode_attention` alone (no layer slab of the ONE attention
    layer's cache either) and converts no weight but the head. Prefill runs
    the scan's forward kernel under `kda.prefill` and writes the group's
    states a slot at a time."""
    from benchmark.harness import spec
    from ray_tpu.models.engine import (decode_slots, init_slot_cache,
                                       prefill_slots)
    from ray_tpu.models.transformer import init_params, serving_params

    bench = spec.load_benchmark()
    conf = spec.load_config(bench, "solar-open2-250b")
    dep = spec.load_traffic("batch-closed-128")["deployment"]
    cfg = spec.build_transformer_config(conf)
    slots = dep["slots"]
    max_len = dep["max_prompt_len"] + dep["max_new_tokens"]
    assert (slots, max_len, cfg.n_layers) == (128, 1280, 4)
    assert cfg.mixer_period == ("attention", "kda", "kda", "kda")
    assert (cfg.moe_experts, cfg.held_experts, cfg.moe_top_k) == (320, 40, 8)
    params = _on(jax.eval_shape(
        lambda k: serving_params(init_params(k, cfg), cfg),
        jax.random.key(0)), one_chip)
    lay = params["layers"]
    assert ["kda_wq" in x for x in lay] == [False, True, True, True]
    assert lay[0]["wg"].shape == (1, 4096, 64, 128)
    assert lay[1]["w_gate"].shape == (1, 40, 4096, 1280)
    assert lay[0]["router"].shape == (1, 4096, 320)
    assert lay[0]["router"].dtype == jnp.float32
    cache = _on(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)), one_chip)
    state, tail = cache["kda_state"], cache["kda_tail"]
    assert state.shape == (3, 128, 64, 128, 128) \
        and state.dtype == jnp.float32
    assert tail.shape == (3, 128, 3, 3 * 8192)
    assert cache["k"].shape == (1, 128, 8, 1280, 128)
    rng = _on(jax.eval_shape(lambda: jax.random.key(0)), one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    decode = decode_slots.lower(params, cache, i32(slots), active, rng, cfg,
                                steps=4).compile()
    assert _device_bytes(decode) < HBM_BYTES
    text = decode.as_text()
    assert re.search(r"%kda_decode_step\S* = [^\n]*custom-call\(", text)
    # (o in q's dtype, which the float32 mixer around it makes float32)
    assert _decode_attention_results(text) == [
        f"f32[{slots},{cfg.kv_heads},{cfg.n_heads // cfg.kv_heads},"
        f"{cfg.head_dim}]"]
    for scope in ("kda.step", "kda.conv", "attn.gate", "moe.experts"):
        assert scope in text, scope
    # a layer's expert stage: the front's branch holds the three rows
    # kernels and nothing beside them that moves an expert stack's weights
    # or a block of them; the whole buffer's keeps `ragged_dot`
    conds = _grouped_matmul_conditionals(text)
    assert len(conds) == cfg.n_layers
    held, d, f = lay[1]["w_gate"].shape[1:]
    weights = "copy|convert|slice|dynamic-slice|fusion|transpose|select"
    for whole, front in conds:
        calls = [x for x in front if "tpu_custom_call" in x]
        assert len(calls) == 3 and all(
            re.search(r"%ragged-dot-rows\S* = ", x) for x in calls), calls
        assert not any("ragged-dot-rows" in x for x in whole)
        assert sum("tpu_custom_call" in x and "ragged-dot" in x
                   for x in whole) >= 3
        for shape in ((held, d, f), (d, f), (d, f // 2), (f, d // 2)):
            assert _sized_ops("\n".join(front), shape, weights) == []
    moved = "copy|convert|select|scatter|dynamic-slice|fusion|transpose"
    assert _sized_ops(text, state.shape, moved) == []
    assert _sized_ops(text, state.shape[1:], moved) == []
    assert _whole_cache_ops(text, cache["k"].shape) == []
    assert _layer_slab_ops(text, cache["k"].shape[1:]) == []
    # (a [slots, d] activation has the dimensions of the rank-128 pairs'
    # [d, 128] matrices: those converts are rows, not weights)
    # (of 2 M numbers or more, and not a row buffer: [slots, ...] rows
    # have the dimensions of the rank-128 pairs' matrices and, times top-k,
    # of `wk`; the convolutions' taps are read as float32, 32 K numbers)
    def weights_converted(text, rows=slots):
        shapes = {tuple(eval(c.split(": ")[1])) for c in _weight_converts(
            text, params, but=params["lm_head"].shape)}
        return sorted(s for s in shapes
                      if np.prod(s) >= 2 << 20 and s[0] != rows)
    assert weights_converted(text) == []
    # nor the head: the chunk multiplies by the bf16 copy the tree holds
    assert _head_sized_ops(text, params["lm_head"]) == []
    # the states are the program's largest buffer and it holds them once:
    # all its temporaries together are smaller than they are
    state_bytes = 4 * int(np.prod(state.shape))
    assert decode.memory_analysis().temp_size_in_bytes < state_bytes

    K, P = 4, dep["max_prompt_len"]
    prefill = prefill_slots.lower(params, cache, i32(K, P), i32(K), i32(K),
                                  rng, cfg).compile()
    assert _device_bytes(prefill) < HBM_BYTES
    text = prefill.as_text()
    kernels = _kernels(text)
    assert any(key.startswith("tpu_custom_call:kda_scan_fwd")
               and "kda.prefill" in scope for key, scope in kernels)
    assert "ragged-dot" in text

    def lower_prefill():
        prefill_slots.clear_cache()
        return prefill_slots.lower(params, cache, i32(K, P), i32(K), i32(K),
                                   rng, cfg)
    _keeps_ragged_dot(monkeypatch, lower_prefill, text)
    assert _sized_ops(text, state.shape, "copy|convert|select|scatter") == []
    assert prefill.memory_analysis().temp_size_in_bytes < state_bytes
    assert weights_converted(text, rows=K * P) == []


def test_phi4flash_serve_programs_compile_and_copy_no_leaf(one_chip, mosaic):
    """The cell `phi-4-mini-flash-reasoning.reason-closed-64` as the
    benchmark runs it (all 32 layers and 200,064 rows, 64 slots x 2,048
    positions, decode chunks of 4, a prefill group of 4 x 1,024): both
    served programs compile for one chip within 15.75 GiB with the weights
    as the engine holds them (bf16, the tied table float32). Decode walks
    three scans (the pattern's segments; the boundary segment's single
    repeat inlined), runs the named kernel on the stacked states where they
    lie (nothing copies, converts, selects over or scatters into a
    whole-state-sized result), reads the ONE full-length K/V leaf through
    `decode_attention` alone (the full layer's call and the scanned cross
    layers', four query rows a key pair, o float32; the masked
    contraction's scores stay for the rings), copies neither that leaf nor
    the rings whole, and converts no weight, the tied head's table
    included: it reads the bf16 copy the tree holds beside it."""
    from benchmark.harness import spec
    from ray_tpu.models.engine import (decode_slots, init_slot_cache,
                                       prefill_slots)
    from ray_tpu.models.transformer import init_params, serving_params

    bench = spec.load_benchmark()
    conf = spec.load_config(bench, "phi-4-mini-flash-reasoning")
    dep = spec.load_traffic("reason-closed-64")["deployment"]
    cfg = spec.build_transformer_config(conf)
    slots = dep["slots"]
    max_len = dep["max_prompt_len"] + dep["max_new_tokens"]
    assert (slots, max_len, cfg.n_layers, cfg.vocab_size) == (
        64, 2048, 32, 200064)
    params = _on(jax.eval_shape(
        lambda k: serving_params(init_params(k, cfg), cfg),
        jax.random.key(0)), one_chip)
    assert params["embed"].dtype == jnp.float32
    assert params["layers"][0][0]["mamba_in"].shape == (8, 2, 2560, 5120)
    cache = _on(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)), one_chip)
    state = cache["mamba_state"]
    assert state.shape == (9, 64, 16, 5120) and state.dtype == jnp.float32
    assert cache["k"].shape == (1, 64, 10, 2048, 128)
    assert cache["win_k"].shape == (8, 64, 10, 512, 128)
    rng = _on(jax.eval_shape(lambda: jax.random.key(0)), one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    decode = decode_slots.lower(params, cache, i32(slots), active, rng, cfg,
                                steps=4).compile()
    assert _device_bytes(decode) < HBM_BYTES
    text = decode.as_text()
    assert re.search(r"%mamba_decode_step\S* = [^\n]*custom-call\(", text)
    for scope in ("mamba.proj", "mamba.conv", "mamba.step", "mamba.out",
                  "gmu", "diff_attn.qkv", "diff_attn.mix", "diff_attn.out"):
        assert scope in text, scope
    moved = "copy|convert|select|scatter|dynamic-slice|fusion|transpose"
    assert _sized_ops(text, state.shape, moved) == []
    assert _sized_ops(text, state.shape[1:], moved) == []
    for name in ("k", "win_k"):
        assert _whole_cache_ops(text, cache[name].shape) == [], name
    # the leaf's eight readers are the kernel's calls (layer 17's and one
    # in each loop that holds cross layers): 4 rows a key pair, float32 for
    # `diff_out` to subtract; nothing materialises the leaf's one layer on
    # the way in, and the contraction's [slots, pairs, 2, 2, positions]
    # scores are there at a ring's 512 places and not at the leaf's 2,048
    G, S, c = cache["k"].shape[2:]
    rows = 2 * cfg.n_heads // cfg.kv_heads
    calls = _decode_attention_results(text)
    assert len(calls) >= 2 and set(calls) == {
        f"f32[{slots},{G},{rows},{c}]"}, calls
    assert _layer_slab_ops(text, cache["k"].shape[1:]) == []
    W = cfg.sliding_window
    assert f"f32[{slots},{G},2,2,{W}]" in text
    for scores in ((slots, G, 2, 2, S), (slots, G, rows, S)):
        assert "[" + ",".join(map(str, scores)) + "]" not in text, scores
    # (of 2 M numbers or more: the scan's A and the convolution's taps
    # are read as float32, 82 K numbers a layer)
    converted = {c.split(": ")[1] for c in _weight_converts(text, params)
                 if np.prod(eval(c.split(": ")[1])) >= 2 << 20}
    assert converted == set()
    # the tied table's bf16 copy is a leaf of the tree (`with_head_copy`):
    # the head multiplies by it and the tokens' rows are gathered from it,
    # so the chunk makes no copy of the table (XLA rounded the WHOLE
    # float32 table once a chunk for both, 1.02 GB of temporaries and 4.6
    # ms of every four substeps) and does not read the float32 leaf at all
    assert _head_sized_ops(text, params["embed"]) == []
    assert params["head_bf16"].shape == params["embed"].shape
    assert "params__head_bf16" in text and "params__embed" not in text
    assert decode.memory_analysis().temp_size_in_bytes < 0.1e9

    K, P = 4, dep["max_prompt_len"]
    prefill = prefill_slots.lower(params, cache, i32(K, P), i32(K), i32(K),
                                  rng, cfg).compile()
    assert _device_bytes(prefill) < HBM_BYTES
    text = prefill.as_text()
    for scope in ("mamba.scan", "mamba.conv", "gmu", "diff_attn.mix"):
        assert scope in text, scope
    assert _sized_ops(text, state.shape, "copy|convert|select|scatter") == []
    assert prefill.memory_analysis().temp_size_in_bytes < 2.0e9
    # the cross-decoder (layers 18-31, the scan over the stack of seven)
    # runs on each row's LAST position: its feed-forward's activation is
    # [K, d_ff] there and [K, P, d_ff] in the self-decoder's scan alone
    comps = _computations(text)
    bodies = [comps[name] for name in set(re.findall(
        r"while\(.*?body=%?([\w.\-]+)", text))]
    cross, own = ([b for b in bodies if any(
        f"bf16[{repeats},{cfg.d_model},{cfg.d_ff}]" in x for x in b)]
        for repeats in (7, 8))
    assert len(cross) == len(own) == 1
    assert _shaped(own[0], K, P, cfg.d_ff) and not _shaped(own[0], K, cfg.d_ff)
    assert _shaped(cross[0], K, cfg.d_ff)
    assert _shaped(cross[0], K, P, cfg.d_ff) == []
    assert _shaped(cross[0], K, P, cfg.mamba_channels) == []


def test_granite_hybrid_serve_programs_compile_and_move_no_state(one_chip,
                                                                 mosaic,
                                                                 monkeypatch):
    """The cell `granite-4.0-h-micro.reason-closed-64` as the benchmark runs
    it (all 40 layers and 100,352 rows, 64 slots x 2,048 positions, decode
    chunks of 4, a prefill group of 4 x 1,024): both served programs compile
    for one chip within 15.75 GiB with the weights as the engine holds them
    (bf16, the tied table float32). Decode runs the named kernel on the
    stacked states where they lie (nothing copies, converts, selects over or
    scatters into a whole-state-sized result), reads the keys and values,
    held as pairs of heads, through `decode_attention` alone, re-tiles
    neither the states nor the tails, and materialises no column slice of a layer's input
    projection; the prefill of 4 x 1,024 walks the prompt's recurrence in
    chunks (no loop of 1,024 trips) and fits."""
    from benchmark.harness import spec
    from ray_tpu.models.engine import (decode_slots, init_slot_cache,
                                       prefill_slots)
    from ray_tpu.models.transformer import init_params, serving_params

    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.mamba2"),
                        "_use_interpret", lambda: False)
    bench = spec.load_benchmark()
    conf = spec.load_config(bench, "granite-4.0-h-micro")
    dep = spec.load_traffic("reason-closed-64")["deployment"]
    cfg = spec.build_transformer_config(conf)
    slots = dep["slots"]
    max_len = dep["max_prompt_len"] + dep["max_new_tokens"]
    assert (slots, max_len, cfg.n_layers, cfg.vocab_size) == (
        64, 2048, 40, 100352)
    params = _on(jax.eval_shape(
        lambda k: serving_params(init_params(k, cfg), cfg),
        jax.random.key(0)), one_chip)
    assert params["embed"].dtype == jnp.float32
    assert params["layers"][0]["mamba2_in"].shape == (4, 2048, 8448)
    assert params["layers"][0]["mamba2_dt"].shape == (4, 2048, 64)
    assert "mamba2_in" not in params["layers"][5]       # the attention layer
    cache = _on(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)), one_chip)
    state, tail = cache["mamba2_state"], cache["mamba2_tail"]
    assert state.shape == (36, 64, 128, 4096) and state.dtype == jnp.float32
    assert tail.shape == (36, 64, 3 * 4352) and tail.dtype == jnp.bfloat16
    # the 8 key/value heads of 64 as 4 pairs of 128: whole lanes
    assert cache["k"].shape == (4, 64, 4, 2048, 128)
    rng = _on(jax.eval_shape(lambda: jax.random.key(0)), one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    decode = decode_slots.lower(params, cache, i32(slots), active, rng, cfg,
                                steps=4).compile()
    assert _device_bytes(decode) < HBM_BYTES
    text = decode.as_text()
    assert re.search(r"%mamba2_decode_step\S* = [^\n]*custom-call\(", text)
    assert not re.search(r"%mamba_decode_step\S* = ", text)
    for scope in ("mamba2.proj", "mamba2.conv", "mamba2.step", "mamba2.out"):
        assert scope in text, scope
    moved = "copy|convert|select|scatter|dynamic-slice|fusion|transpose"
    assert _sized_ops(text, state.shape, moved) == []
    assert _sized_ops(text, state.shape[1:], moved) == []
    # the tails' stack is updated in place: no copy re-tiles it between two
    # layers' updates (as [.., 3, 4352], padded to 4 rows, XLA did, twice a
    # layer), and the input projection is read whole, never as a column
    # slice written out first
    assert _sized_ops(text, tail.shape, "copy|convert|transpose") == []
    assert _sized_ops(text, (2048, 4352), "copy|fusion|dynamic-slice") == []
    # the four attention layers read their pairs through the decode kernel,
    # 8 query rows a pair ([q | 0] four times, [0 | q] four times); nothing
    # slices a layer's slab out of the stack first
    calls = _decode_attention_results(text)
    assert calls and set(calls) == {f"bf16[{slots},4,8,128]"}, calls
    assert _layer_slab_ops(text, cache["k"].shape[1:]) == []
    assert _whole_cache_ops(text, cache["k"].shape) == []
    # the temporaries: no second copy of the tied table, no slab of states
    assert decode.memory_analysis().temp_size_in_bytes < 0.3e9
    assert _head_sized_ops(text, params["embed"]) == []
    assert "params__head_bf16" in text

    # a decode step whose cache is NOT donated (the benchmark's check reads
    # a step's logits so) may not write into the 4.8 GB of states it was
    # given, and a copy of them does not fit beside them: `_decode_one`
    # without ``active`` hands each layer's new states back and copies none
    from ray_tpu.models.engine import _decode_one

    kept = jax.jit(lambda p, c, t: _decode_one(p, c, t, cfg)[1]).lower(
        params, cache, i32(slots)).compile()
    assert _device_bytes(kept) < HBM_BYTES
    assert kept.memory_analysis().temp_size_in_bytes < 0.5e9
    assert _sized_ops(kept.as_text(), state.shape, "copy") == []

    K, P = 4, dep["max_prompt_len"]
    prefill = prefill_slots.lower(params, cache, i32(K, P), i32(K), i32(K),
                                  rng, cfg).compile()
    assert _device_bytes(prefill) < HBM_BYTES
    text = prefill.as_text()
    for scope in ("mamba2.scan", "mamba2.conv", "mamba2.proj", "mamba2.out"):
        assert scope in text, scope
    assert _sized_ops(text, state.shape, "copy|convert|select|scatter") == []
    assert prefill.memory_analysis().temp_size_in_bytes < 2.0e9
    # the loops: the pattern's 4 periods and the bucket's 4 chunks of 256;
    # nothing steps through the 1,024 positions
    comps = _computations(text)
    conditions = set(re.findall(r"while\(.*?condition=%?([\w.\-]+)", text))
    assert len(conditions) == 10        # the periods, a mamba2 layer's chunks
    for name in conditions:
        bounds = [int(n) for line in comps[name]
                  for n in re.findall(r"s32\[\]\S* constant\((\d+)\)", line)]
        assert bounds and max(bounds) <= 4, (name, bounds)

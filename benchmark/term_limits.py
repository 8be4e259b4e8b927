"""How the limits of a train cell's compared numbers were read on the chip:
over a list of seeds, in ONE process that holds the chip, the numbers the
cell's check compares (`train_cell.check_against_reference`: logits, the
cross entropy, every further term of the objective) for the program as the
configuration states it, and for the CONTROL: the configuration's plain
reference put in the program's place with its weights rounded to the nearest
precision below the stated one (bfloat16 -> float8 e4m3; float32 ->
bfloat16), compared with the same reference on the weights as they are.

    python benchmark/term_limits.py --workload olmoe-1b-7b.train-4k \
        --seeds 3100000101,3100000102,...

One JSON line a seed and a last line with, per number, the largest program
reading (the limit has to lie above it) and the smallest control reading
(and below that). One-chip train cells only (no mesh is built). Nothing
here is a cell: PERF.md quotes the lines beside each limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import spec  # noqa: E402


def _below(dtype_name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.float8_e4m3fn, "float32": jnp.bfloat16}[
        dtype_name]


def read_seed(cell, conf, traffic, arch, seed: int, root: str) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.harness import reference, train_cell
    from ray_tpu.models.transformer import init_params

    seq = traffic["check"].get("seq_len", traffic["seq_len"])
    rows = traffic["check"]["rows"]
    fields = spec.transformer_fields(conf, root)
    cfg = spec.build_transformer_config(
        conf, root, max_seq_len=traffic["seq_len"],
        param_dtype=traffic["param_dtype"],
        attention_impl=traffic["attention_impl"])
    params = jax.jit(lambda k: init_params(k, cfg))(
        jax.random.key(spec.seed32(seed)))
    got = train_cell.check_against_reference(
        params, cfg, fields, conf, arch, None, seed, rows, seq)
    program = {"logits_rel_rms": got["logits"]["rel_rms_error"]}
    program.update({f"{k}_abs_diff": t["abs_diff"]
                    for k, t in got["objective"]["terms"].items()})
    # the control: the reference on weights rounded one precision down,
    # against the reference on the weights as they are
    dtype = jnp.dtype(cfg.dtype).name
    low = _below(dtype)
    rounded = jax.tree.map(lambda a: a.astype(low).astype(a.dtype), params)
    tokens = train_cell._make_batch(seed ^ 0x5EED, 0, rows, seq,
                                    cfg.vocab_size)["tokens"]
    terms = getattr(arch, "reference_terms", None)
    control = {"logits_rel_rms": 0.0}
    sums: dict = {}
    for r in range(rows):
        sides = []
        for tree in (params, rounded):
            logits = arch.reference_logits(tree, tokens[r, :-1], fields,
                                           conf)
            side = {"loss": float(reference.reference_loss(
                logits, tokens[r, 1:]))}
            if terms is not None:
                side.update({k: float(v) for k, v in terms(
                    tree, tokens[r], fields, conf).items()})
            sides.append((logits, side))
        control["logits_rel_rms"] = max(
            control["logits_rel_rms"],
            reference.rel_rms_error(sides[1][0], sides[0][0]))
        for k in sides[0][1]:
            sums[k] = sums.get(k, 0.0) + (sides[1][1][k] - sides[0][1][k])
        del sides, logits
    control.update({f"{k}_abs_diff": abs(v) / rows for k, v in sums.items()})
    return {"seed": seed, "program": program, "control": control,
            "program_ok": got["ok"],
            "reference": {k: t["reference"]
                          for k, t in got["objective"]["terms"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    if traffic["kind"] != "train" or traffic.get("mesh"):
        raise SystemExit("one-chip train cells only")
    arch = spec.load_architecture(conf)
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        lines.append(read_seed(cell, conf, traffic, arch, seed, spec.ROOT))
        print(json.dumps(lines[-1]), flush=True)
    summary = {"cell": cell["name"], "seeds": len(lines), "numbers": {
        k: {"program_max": max(l["program"][k] for l in lines),
            "control_min": min(l["control"][k] for l in lines)}
        for k in lines[0]["program"]}}
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "a") as f:
            for l in lines + [summary]:
                f.write(json.dumps(l) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

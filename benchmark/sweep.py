"""Rate sweep of an open-loop serve cell: finds the knee ONCE, on the chip.

    python benchmark/sweep.py --workload internlm2-1.8b.chat-steady \\
        --rates 3,4,5,6,7,8 --seconds 25 --seed 7

One deployment, warmed once, then the cell's own generator at each rate in
turn (ramp, window, tail, drain). Prints one JSON line per rate with the
first-token and inter-token distributions, the share of requests inside
the limits given (`--ttft-ms`, `--tpot-ms`), and whether a backlog grew
(first-token time of the window's last third against its first third).
The knee is the highest rate at which at least 90% of requests meet both
limits and no backlog grows; the cell's traffic file then states 0.6 of it
as a number. Nothing here is a benchmark result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import spec, stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--ttft-ms", type=float, default=2500.0)
    ap.add_argument("--tpot-ms", type=float, default=60.0)
    args = ap.parse_args(argv)
    args.trace = 0
    import ray_tpu
    from ray_tpu import serve

    from benchmark.harness import serve_cell

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    vocab = spec.transformer_fields(conf)["vocab_size"]
    ray_tpu.init()
    try:
        handle, info = serve_cell.deploy(conf, traffic, platform="tpu")
        print(json.dumps({"device": info["device"],
                          "warm": serve_cell.call(handle, "bench_warm")}),
              flush=True)
        for rate in [float(r) for r in args.rates.split(",")]:
            t = dict(traffic, rate_per_s=rate)
            sched, records, extra = serve_cell.run_open_loop(
                handle, t, vocab, args, None)
            c = serve_cell.reduce_open_loop(sched, records)
            counted = sorted((r for r in records if r["counted"]
                              and r["first_s"] is not None),
                             key=lambda r: r["due_s"])
            ttft = [(r["first_s"] - r["due_s"]) * 1e3 for r in counted]
            third = max(1, len(ttft) // 3)
            tpot_of = {id(r): (r["last_s"] - r["first_s"]) / (r["n"] - 1)
                       * 1e3 for r in counted if r["n"] > 1}
            met = sum(1 for r, x in zip(counted, ttft)
                      if x <= args.ttft_ms
                      and tpot_of.get(id(r), 0.0) <= args.tpot_ms)
            eng = extra["counters"]["engine"]
            print(json.dumps({
                "rate_per_s": rate, "requests": c["attempted"],
                "failed": c["failed"],
                "met_both_limits_share": met / max(1, c["attempted"]),
                "limits_ms": [args.ttft_ms, args.tpot_ms],
                "ttft_ms": {k: stats.percentile(ttft, q) for k, q in
                            (("p10", 10), ("p50", 50), ("p90", 90),
                             ("p95", 95), ("p99", 99))},
                "ttft_mean_ms": stats.mean(ttft),
                "ttft_first_third_mean_ms": stats.mean(ttft[:third]),
                "ttft_last_third_mean_ms": stats.mean(ttft[-third:]),
                "tpot_ms": {k: stats.percentile(c["tpot_ms"], q)
                            for k, q in (("p50", 50), ("p90", 90),
                                         ("p95", 95), ("p99", 99))},
                "late_p99_ms": stats.percentile(c["late_ms"], 99),
                "occupancy": (eng["tokens_out"] - eng["prefills"])
                / max(1, eng["decode_steps"] * 32),
                "engine": eng,
                "drain_s": (c["last_done_s"] or 0) - sched["window_s"],
            }), flush=True)
            time.sleep(2.0)   # let abandoned tail requests leave the slots
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run: starts a local ray_tpu cluster, runs the cell in the
worker (train) or replica (serve) that leases the chip(s), and prints ONE
last line of JSON: `correct`, `attempted`, `failed`, `metrics`, `device`
(and `breakdown` in a traced run; a closed loop adds `tokens_made`, what
its rate counts, and `tokens_whole_requests`, the count the rate was until
PR 56, which decides nothing), then `compared`: each number the run
compared beside its limit, which the last line on standard error repeats.
Earlier lines are information. With
`--trace 0` the metrics are the cell's end-to-end metrics, timed with the
profiler off; with `--trace 1` they are its per-layer metrics.

This process never touches JAX: the chip belongs to the worker. Without a
TPU (or with a `device_kind` that `benchmark/peaks.json` does not list)
the run fails: non-zero exit, no result line on stdout, and a
`{"correct": false, ...}` line on stderr saying why. There is no CPU
fallback on this path; the tests drive the same functions at toy size
through `run_cell(platform="cpu")`.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import time
import traceback

T_PROCESS_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec, stats  # noqa: E402


class BenchFailure(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, ...)."""


def _info(**fields):
    print(json.dumps(fields), flush=True)


def end_to_end_values(kind: str, out: dict, setup_s: float) -> dict:
    """Every end-to-end number the run can state, by metric name."""
    vals = {"setup_s": setup_s}
    if kind == "train":
        vals["train_tokens_per_s"] = stats.rate(out["tokens"],
                                                out["window_s"])
    elif kind == "open_loop":
        vals["tpot_p50_ms"] = stats.percentile(out["client"]["tpot_ms"], 50)
    elif kind == "closed_loop":
        vals["serve_tokens_per_s"] = closed_loop_counts(out)[
            "tokens_made"]["per_s"]
    return vals


def closed_loop_counts(out: dict) -> dict:
    """A closed loop's two counts of its window, each with its seconds and
    its rate. `tokens_made` is `serve_tokens_per_s`: the engine's
    `tokens_out` at the read that closes the window less at the read that
    opens it, over the seconds between the two reads on the replica's own
    clock. `tokens_whole_requests` is what the metric was until PR 56, the
    tokens of the whole requests that ENDED in the window by the client's
    clock: it decides nothing, and stands beside the rate in every run's
    line."""
    c, client = out["counters"], out["client"]
    counts = {"tokens_made": (c["engine"]["tokens_out"],
                              c["clock_s"] - out["mark"]["clock_s"]),
              "tokens_whole_requests": (client["tokens"],
                                        client["window_s"])}
    return {name: {"tokens": n, "window_s": s, "per_s": stats.rate(n, s)}
            for name, (n, s) in counts.items()}


def compared_numbers(check: dict) -> dict:
    """{short name: [number, its limit]} of every number the reference
    check compared (`out["check"]` of either kind of cell): what the last
    line carries under `compared` and the last line on standard error
    repeats."""
    if "logits" in check:           # a train cell
        out = {"logits_rel_rms": [check["logits"]["rel_rms_error"],
                                  check["logits"]["tolerance"]]}
        objective = check["objective"]
        for name, term in objective["terms"].items():
            out[f"{name}_abs_diff"] = [term["abs_diff"], term["tolerance"]]
        if "weighted_sum" in objective:
            out["total_minus_weighted_sum_rel"] = [
                objective["weighted_sum"]["rel_diff"],
                objective["weighted_sum"]["tolerance"]]
        return out
    out = {}                        # a serve cell: the worst prompt
    for part in ("prefill", "decode"):
        worst = max((r[part] for r in check["rows"]),
                    key=lambda r: r["rel_rms_error"])
        out[f"{part}_logits_rel_rms"] = [worst["rel_rms_error"],
                                         worst["tolerance"]]
    out["served_tokens_not_the_references"] = [
        sum(not r["served_tokens_ok"] for r in check["rows"]), 0]
    return out


def compared_of_run(out: dict) -> dict:
    """`compared_numbers` of the reference check and, for a closed loop,
    the whole run's identity: the tokens the engine counted less the
    tokens the clients received, which is 0."""
    compared = compared_numbers(out["check"])
    if "whole_run" in out:
        run = out["whole_run"]
        compared["tokens_made_minus_tokens_received"] = [
            abs(run["engine_tokens_out"] - run["client_tokens"]), 0]
    return compared


def run_cell(bench: dict, cell: dict, args, *, root: str = spec.ROOT,
             platform: str = "tpu", field_overrides=None,
             traffic_overrides=None) -> dict:
    """Everything between cluster start and cluster stop for one cell;
    returns the last line's object. ``platform="cpu"`` with overrides is
    the tests' toy-size path; the command line always passes "tpu"."""
    from benchmark.harness import serve_cell, train_cell

    conf = spec.load_config(bench, cell["config"], root)
    traffic = spec.load_traffic(cell["traffic"], root)
    traffic.update(traffic_overrides or {})
    kind = traffic["kind"]
    trace_dir = os.path.join(root, ".bench_out", "trace", cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    mod = train_cell if kind == "train" else serve_cell
    out = mod.run(cell, conf, traffic, args, root=root, platform=platform,
                  field_overrides=field_overrides, trace_dir=trace_dir)
    device = out["device"] if kind == "train" else out["info"]["device"]
    if platform == "tpu":
        peaks = spec.device_peaks(device["kind"], root)  # unknown: error
    else:
        peaks = None
    if device["platform"] != platform or device["count"] < cell["chips"]:
        raise BenchFailure(f"cell {cell['name']} needs {cell['chips']} "
                           f"{platform} device(s); the worker saw {device}")
    setup_s = out["window_open_unix"] - T_PROCESS_START
    checks = train_cell.judge(out, traffic, platform) if kind == "train" \
        else serve_cell.judge(out)
    e2e = end_to_end_values(kind, out, setup_s)
    if kind == "train":
        attempted, failed = out["steps"], 0
        peak = out["memory_peak_bytes"]
        compilations = out["compilations_in_window"]
    else:
        attempted = out["client"]["attempted"]
        failed = out["client"]["failed"]
        peak = out["counters"]["memory_peak_bytes"]
        compilations = out["counters"]["compilations"]
    fields = spec.transformer_fields(conf, root)
    fields.update(field_overrides or {})
    _info(cell=cell["name"], device=device, checks=checks,
          compilations_in_window=compilations,
          check=out.get("check"), memory_peak_bytes=peak,
          chip_worker_ready_s=out["chip_worker_ready_s"],
          **{k: out[k] for k in (
              "compile_s", "check_s", "program_argument_bytes",
              "program_temp_bytes", "state_bytes", "steps", "window_s",
              "step_metrics", "warm", "info", "repeat", "n_requests_sent",
              "slow_events", "sleeper", "whole_run", "trace_stop",
              "trace_reduce_s") if k in out},
          first_token_ms={
              "mean": stats.mean(out["client"]["ttft_ms"]),
              **{f"p{q}": stats.percentile(out["client"]["ttft_ms"], q)
                 for q in (50, 90, 95)}} if kind == "open_loop" else None,
          engine=(out.get("counters") or {}).get("engine"),
          health=(out.get("client") or {}).get("health"),
          trace={k: v for k, v in (out.get("trace") or {}).items()
                 if k != "op_seconds"} if args.trace else None)
    dev_line = {"platform": device["platform"], "kind": device["kind"],
                "count": device["count"], "memory_peak_bytes": peak}
    line = {"correct": all(checks.values()), "attempted": attempted,
            "failed": failed, "metrics": {}, "device": dev_line}
    declared = {m["name"]: m for m in
                spec.metrics_for(bench, cell["name"], "end_to_end")}
    if not args.trace:
        for name, m in declared.items():
            if e2e.get(name) is None:
                raise BenchFailure(f"cell {cell['name']}: no value for "
                                   f"end-to-end metric {name}")
            line["metrics"][name] = {"value": float(e2e[name]),
                                     "unit": m["unit"]}
    else:
        trace = out.get("trace") or {}
        evidence = {"cell": cell, "conf": conf, "traffic": traffic,
                    "fields": fields, "peaks": peaks, "out": out,
                    "trace": trace, "end_to_end": e2e, "kind": kind,
                    "root": root}
        line["metrics"] = spec.read_layer_metrics(bench, cell["name"],
                                                  evidence, root)
        dev_line["busy_s"] = trace.get("busy_s")
        dev_line["window_s"] = trace.get("window_s")
        line["breakdown"] = {"device_ops": trace.get("device_ops", []),
                             "idle_gaps": trace.get("idle_gaps", [])}
        line["end_to_end"] = {k: v for k, v in e2e.items()
                              if k in declared}
        if not trace.get("busy_s"):
            line["correct"] = False  # a traced run must see the device
    if kind == "closed_loop":
        line.update(closed_loop_counts(out))
    line["compared"] = compared_of_run(out)
    return line


def _session_dir():
    """The cluster's session directory under this run's own TMPDIR (the
    program's default is a fixed /tmp/ray_tpu/...), fresh for every run;
    None (the default) where that path would be too long for the unix
    sockets kept in it."""
    import tempfile

    path = os.path.join(tempfile.gettempdir(), f"rtb_{os.getpid()}")
    if len(path) > 70:
        return None
    shutil.rmtree(path, ignore_errors=True)
    return path


def _descendants(root_pid: int) -> list:
    parent = {}
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                rest = f.read().rsplit(")", 1)[1].split()
            if rest[0] != "Z":
                parent[int(pid)] = int(rest[1])
        except (OSError, IndexError, ValueError):
            continue
    out, frontier = [], [root_pid]
    while frontier:
        p = frontier.pop()
        kids = [c for c, pp in parent.items() if pp == p]
        out.extend(kids)
        frontier.extend(kids)
    return out


def _reap_children() -> dict:
    """Wait for, then end, whatever this run started and left behind;
    says how long it waited and what it had to kill."""
    me, t0 = os.getpid(), time.time()
    while _descendants(me) and time.time() - t0 < 10:
        time.sleep(0.2)
    killed = _descendants(me)
    for pid in killed:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    return {"waited_s": time.time() - t0, "killed": len(killed)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line, rc = None, 1
    try:
        bench = spec.load_benchmark()
        cell = spec.find_cell(bench, args.workload)
        if args.seconds is None:
            args.seconds = float(bench["run_seconds"])
        if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
            raise BenchFailure("JAX_PLATFORMS=cpu: the measuring path needs "
                               "a TPU; there is no CPU fallback")
        import ray_tpu

        session_dir = _session_dir()
        ray_tpu.init(session_dir=session_dir)
        try:
            have = ray_tpu.cluster_resources().get("TPU", 0)
            if have < cell["chips"]:
                raise BenchFailure(
                    f"this host offers {have} TPU chip(s), cell "
                    f"{cell['name']} needs {cell['chips']}")
            line = run_cell(bench, cell, args)
        finally:
            ray_tpu.shutdown()
            if session_dir:
                shutil.rmtree(session_dir, ignore_errors=True)
        rc = 0 if line["correct"] else 1
    except Exception as e:  # noqa: BLE001 — reported, exit code non-zero
        traceback.print_exc()
        print(json.dumps({"correct": False, "attempted": 0, "failed": 0,
                          "metrics": {}, "device": None,
                          "error": f"{type(e).__name__}: {e}"[:500]}),
              file=sys.stderr, flush=True)
        line = None
    if "jax" in sys.modules:
        print("benchmark/run.py: the driver process imported jax",
              file=sys.stderr)
        line, rc = None, 1
    reaped = _reap_children()
    if line is not None:
        _info(shutdown=reaped)
        print(json.dumps({"compared": line["compared"]}), file=sys.stderr,
              flush=True)
        print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

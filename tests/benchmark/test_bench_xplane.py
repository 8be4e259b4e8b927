"""The trace reduction: interval arithmetic and attribution on events made
by hand, then the whole reduction on the trace recorded on the chip and
kept under benchmark/fixtures/."""

import json
import os
import re

import pytest

import bench_paths
from benchmark.harness import xplane

MS = 1_000_000  # ns


@pytest.mark.parametrize("ivs,want", [
    ([(0, 5), (3, 8)], [(0, 8)]),
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)]),
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)]),
    ([(0, 1), (1, 2)], [(0, 2)]),
    ([(3, 3)], [])])
def test_union(ivs, want):
    assert xplane.union(ivs) == want
    assert xplane.total(xplane.union(ivs)) == sum(e - s for s, e in want)


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 4), (6, 9)], [(3, 7)], [(0, 3), (7, 9)]),
    ([(0, 4)], [(0, 4)], []),
    ([(2, 4)], [(0, 10)], [])])
def test_subtract(a, b, want):
    assert xplane.subtract(a, b) == want


@pytest.mark.parametrize("name,key", [
    ("%fusion.165 = bf16[32,8]{1,0} fusion(%p0)", "fusion.165"),
    ("copy.62", "copy.62"),
    ("all-gather-start.3", "all-gather-start.3"),
    ("%while.2 = (s32[]) while(...)", "while.2"),
    ('%closed_call.9 = bf16[4]{0} custom-call(%x), '
     'custom_call_target="tpu_custom_call"', "tpu_custom_call:closed_call.9"),
    ('%cc.1 = bf16[4]{0} custom-call(%x), custom_call_target="Sharding"',
     "cc.1")])
def test_operation_names(name, key):
    assert xplane.op_key(name) == key


def test_self_time_takes_children_off_their_parent():
    events = [("while.1", 0, 100), ("fusion.1", 10, 30),
              ("fusion.2", 50, 40), ("copy.1", 55, 5), ("after.1", 120, 7)]
    got = dict(xplane.self_times(events))
    assert got == {"while.1": 30, "fusion.1": 30, "fusion.2": 35,
                   "copy.1": 5, "after.1": 7}


def _planes():
    ops0 = [("fusion.1", 0, 4 * MS), ("all-reduce.1", 4 * MS, 2 * MS),
            ("fusion.2", 10 * MS, 5 * MS)]
    ops1 = [("fusion.1", 0, 5 * MS), ("all-reduce.1", 5 * MS, 1 * MS),
            ("fusion.2", 10 * MS, 5 * MS)]
    host = {"thread-1": [("bench:train.make_batch", 6 * MS, 3 * MS),
                         ("bench:train.dispatch", 9 * MS, 2 * MS),
                         ("python frame", 0, 20 * MS)]}
    return {"/device:TPU:0": {"XLA Ops": ops0,
                              "XLA Modules": [("jit_step(1)", 0, 15 * MS)]},
            "/device:TPU:1": {"XLA Ops": ops1,
                              "XLA Modules": [("jit_step(1)", 0, 15 * MS)]},
            "/host:CPU": host}


def test_reduction_of_two_hand_made_devices():
    red = xplane.reduce_planes(_planes(), window=(0, 15 * MS))
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(0.015)
    assert red["busy_s_per_device"] == pytest.approx([0.011, 0.011])
    assert red["busy_s"] == pytest.approx(0.011)
    # a collective alone on the op line is exposed: 2 ms and 1 ms
    assert red["collective_exposed_s"] == pytest.approx(0.0015)
    assert red["op_seconds"]["fusion.1"] == pytest.approx(0.0045)
    assert red["device_ops"][0][0] == "fusion.2"
    # the 4 ms gap [6, 10) ms: 3 ms under make_batch, 1 under dispatch
    assert red["idle_gaps"] == [["train.make_batch", pytest.approx(0.004)]]
    assert red["modules"]["jit_step"]["count"] == 1


@pytest.mark.parametrize("window,busy", [((0, 15 * MS), 0.011),
                                         ((2 * MS, 12 * MS), 0.006)])
def test_window_clips_busy_time(window, busy):
    red = xplane.reduce_planes(_planes(), window=window)
    assert red["busy_s"] == pytest.approx(busy)


def test_overlapped_collective_is_not_exposed():
    planes = {"/device:TPU:0": {"XLA Ops": [
        ("fusion.1", 0, 10 * MS), ("all-gather.1", 2 * MS, 3 * MS)]}}
    red = xplane.reduce_planes(planes)
    assert red["collective_exposed_s"] == 0
    assert red["busy_s"] == pytest.approx(0.010)


def test_a_gap_no_span_covers_is_named_so():
    planes = {"/device:TPU:0": {"XLA Ops": [
        ("fusion.1", 0, MS), ("fusion.2", 3 * MS, MS)]}}
    red = xplane.reduce_planes(planes)
    assert red["idle_gaps"] == [[xplane.UNATTRIBUTED,
                                 pytest.approx(0.002)]]


def test_no_device_plane_reduces_to_no_devices():
    assert xplane.reduce_planes({"/host:CPU": {"t": [("x", 0, 5)]}}) == \
        {"devices": 0}
    assert xplane.reduce_trace("/nonexistent/dir") == {"devices": 0}


# ---- the trace recorded on the chip ---------------------------------------

FIXTURES = os.path.join(bench_paths.REPO, "benchmark", "fixtures")
TRACE = os.path.join(FIXTURES, "train_tiny_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(FIXTURES, "train_tiny_v5e.facts.json")) as f:
        facts = json.load(f)
    return facts, xplane.reduce_planes(xplane.load_planes(TRACE))


def test_fixture_is_small_enough_to_keep():
    assert os.path.getsize(TRACE) < 400_000


def test_recorded_trace_busy_and_idle(recorded):
    facts, red = recorded
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    # three steps with a 20 ms host sleep before the last: the device sat
    # idle for at least that long inside the window
    assert red["window_s"] - red["busy_s"] >= 0.020
    assert red["busy_s"] == pytest.approx(facts["reduced"]["busy_s"])
    assert red["window_s"] == pytest.approx(facts["reduced"]["window_s"])


def test_recorded_trace_per_operation_time(recorded):
    facts, red = recorded
    assert sum(red["op_seconds"].values()) == pytest.approx(
        red["busy_s"], rel=0.02)
    step = [v for k, v in red["modules"].items() if "step" in k]
    assert step and step[0]["count"] == facts["steps"]
    pattern = facts["attention_op_pattern"]
    k = xplane.op_seconds_matching(red, pattern)
    assert 0 < k < red["busy_s"]
    # forward, the remat's second forward, dq and dk/dv: four kernel calls
    # a layer and step, each a custom-call to tpu_custom_call
    n = sum(c for name, c in red["op_count"].items()
            if re.search(pattern, name))
    assert n == facts["attention_kernel_calls"] == \
        4 * facts["layers"] * facts["steps"]
    assert [k for k, _ in red["device_ops"]][0].startswith(
        "tpu_custom_call:")


def test_recorded_trace_gap_attribution(recorded):
    _, red = recorded
    gaps = dict(red["idle_gaps"])
    assert gaps.get("fixture.sleep", 0) >= 0.018
    assert max(gaps, key=gaps.get) == "fixture.sleep"
    spans = red["host_spans"]
    assert spans["train.dispatch"]["count"] == 3
    assert spans["fixture.sleep"]["seconds"] >= 0.020


@pytest.mark.parametrize("reader,metric,evidence_extra,lo,hi", [
    ("kernel_share", {"op_pattern": "^tpu_custom_call:"}, {}, 5, 60),
    ("train_step_device", {}, {}, 0.1, 1.0),
])
def test_readers_on_the_recorded_trace(recorded, reader, metric,
                                       evidence_extra, lo, hi):
    from benchmark.harness import spec

    facts, red = recorded
    read = spec.load_reader({"reader": reader})
    evidence = {"trace": red, "out": {"trace_steps": facts["steps"]}}
    evidence.update(evidence_extra)
    assert lo < read(evidence, metric) < hi


def test_roofline_reader_on_the_recorded_trace(recorded):
    """The tiny model's kernel share of its roofline, from the recorded
    kernel time and the hand-countable work: a share, so under 100."""
    from benchmark.harness import spec

    facts, red = recorded
    read = spec.load_reader({"reader": "kernel_roofline"})
    evidence = {
        "trace": red, "out": {"trace_steps": facts["steps"]},
        "peaks": spec.device_peaks(facts["device_kind"]),
        "fields": {"d_model": 256, "n_heads": 2,
                   "n_layers": facts["layers"]},
        "traffic": {"rows": facts["rows"], "seq_len": facts["seq"]},
        "cell": {"chips": 1}}
    # at 512 x 128 the peaks table says memory, not compute, bounds it
    with pytest.raises(ValueError, match="bound"):
        read(evidence, {"op_pattern": "^tpu_custom_call:",
                        "bound": "compute", "name": "x"})
    share = read(evidence, {"op_pattern": "^tpu_custom_call:",
                            "bound": "memory", "name": "x"})
    assert 0 < share < 100


@pytest.mark.parametrize("cell,steps,window_s,lo,hi", [
    # the steps and trace windows the chip printed for these cells (PERF.md
    # section 5): 5 steps in 7.58 s on one chip, 5 in 16.92 s on four
    ("internlm2-1.8b.train-4k", 5, 7.5806, 47.0, 50.0),
    ("mistral-7b-v0.3.train-fsdp2tp2", 5, 16.923, 41.0, 44.0),
    # half the time, twice the share; no steps in the trace, no number
    ("internlm2-1.8b.train-4k", 5, 3.7903, 94.0, 100.0),
    ("internlm2-1.8b.train-4k", 0, 7.5806, None, None),
])
def test_train_mfu_reader_is_tokens_of_the_traced_steps_over_the_trace(
        cell, steps, window_s, lo, hi):
    """The reader's arithmetic at the cells' real sizes, on hand-made
    evidence: required FLOPs per token x (steps x rows x seq_len) over
    the trace's own window, over chips x the peaks table's bf16 peak."""
    from benchmark.harness import spec

    bench = spec.load_benchmark()
    c = spec.find_cell(bench, cell)
    traffic = spec.load_traffic(c["traffic"])
    conf = spec.load_config(bench, c["config"])
    fields = spec.transformer_fields(conf)
    peaks = spec.device_peaks("TPU v5 lite")
    read = spec.load_reader({"reader": "train_mfu"})
    got = read({"trace": {"window_s": window_s},
                "out": {"trace_steps": steps}, "traffic": traffic,
                "fields": fields, "conf": conf, "peaks": peaks, "cell": c},
               {})
    if lo is None:
        assert got is None
        return
    assert lo < got < hi
    tokens = steps * traffic["rows"] * traffic["seq_len"]
    dense = spec.load_architecture({})   # the dense count, by name
    by_hand = 100.0 * 3.0 * dense.forward_flops_per_token(
        fields, conf, traffic["seq_len"]) * tokens / window_s \
        / (c["chips"] * peaks["bf16_flops_per_s"])
    assert got == pytest.approx(by_hand, rel=1e-9)

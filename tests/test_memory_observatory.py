"""Object-plane memory observatory (r20): per-node arena accounting,
per-job/per-owner attribution, the `ray_tpu memory` CLI, and leak
detection.

Ref analogs: `ray memory` / memory_utils.py's grouped object table and
the dashboard memory view; the reference serves them from GCS object
tables, here the sharded head directory + per-node arena heartbeats
answer the same questions. The warning helpers are factored pure so the
leak/pressure/dead-owner paths are exercised deterministically —
crafted snapshots, no sleeps (ISSUE 20 acceptance)."""

import json
import time
import urllib.request
from argparse import Namespace

import numpy as np
import pytest

import ray_tpu
from ray_tpu import state as state_api
from ray_tpu.core.config import Config
from ray_tpu.core.ids import ObjectID
from ray_tpu.core.object_store import ShmObjectStore
from ray_tpu.dashboard import _arena_growth_warnings, _memory_warnings


@pytest.fixture
def store():
    s = ShmObjectStore(f"rtpu_test_{ObjectID.from_random().hex()[:8]}",
                       32 * 1024 * 1024, create=True)
    yield s
    s.close()


# ====================================================== store accounting


def test_memory_stats_sealed_bytes_exact(store):
    """sealed_bytes counts exactly data+meta payload — the invariant the
    head-side byte attribution depends on (OBJECT_SEALED reports the
    same number, so directory sums equal store ground truth)."""
    from ray_tpu.core import serialization

    arr = np.arange(2048, dtype=np.float64)
    sv = serialization.serialize(arr)
    oid = ObjectID.from_random()
    sealed = store.put_serialized(oid, sv.frames)
    assert sealed == store.sealed_nbytes(sv.frames)
    m = store.memory_stats()
    assert m["sealed_count"] == 1
    assert m["sealed_bytes"] == sealed
    # data-only view matches the wire/directory size convention
    # (sv.total_bytes); the delta is the pickled frame-size metadata
    assert m["sealed_data_bytes"] == sv.total_bytes
    assert m["sealed_bytes"] > m["sealed_data_bytes"]
    assert m["entries"] == 1
    # capacity is the usable arena: the 32MiB segment minus the header
    # + object-table overhead
    assert 0 < m["capacity"] <= 32 * 1024 * 1024
    # used_bytes includes the allocator block header, so it bounds the
    # payload from above; highwater tracks the peak fill
    assert m["used_bytes"] >= sealed
    assert m["highwater_bytes"] >= m["used_bytes"]


def test_memory_stats_highwater_survives_free(store):
    oid = ObjectID.from_random()
    buf = store.create(oid, 1 << 20)
    buf[:] = b"\0" * (1 << 20)
    del buf
    store.seal(oid)
    peak = store.memory_stats()["highwater_bytes"]
    assert peak >= 1 << 20
    store.release(oid)
    assert store.delete(oid)
    m = store.memory_stats()
    assert m["used_bytes"] < peak          # arena actually drained
    assert m["highwater_bytes"] == peak    # ...but the peak is sticky


def test_memory_stats_borrow_and_deferred_delete(store):
    """A zero-copy borrow shows up as borrow-pinned bytes; deleting a
    borrowed object defers (deferred_deletes + age stamp) until the
    view dies, then reap drains the ledger."""
    from ray_tpu.core import serialization

    arr = np.arange(5000, dtype=np.uint8)
    sv = serialization.serialize(arr)
    oid = ObjectID.from_random()
    store.put_serialized(oid, sv.frames)
    frames = store.get_frames(oid, pin_borrows=True)
    out = serialization.deserialize(frames)
    store.release(oid)  # read pin off; borrow pin rides `out`
    m = store.memory_stats()
    assert m["borrow_pinned_count"] == 1
    assert m["borrow_pinned_bytes"] >= 5000
    assert m["deferred_deletes"] == 0
    assert store.delete(oid) is False  # deferred behind the live view
    m = store.memory_stats()
    assert m["deferred_deletes"] == 1
    assert m["deferred_delete_oldest_s"] >= 0.0
    del out, frames
    store.reap_borrows()
    m = store.memory_stats()
    assert m["deferred_deletes"] == 0
    assert m["borrow_pinned_count"] == 0


# ============================================ leak detection (pure units)


def _cfg(**kw):
    return Config(**kw)


def _series(pts):
    return {"kind": "gauge", "points": pts}


def _mono_history(n=10, cap=1 << 30, start=0.0, step=0.1 * (1 << 30)):
    """Monotone arena fill: n points, 15s apart, growing `step` each."""
    pts = [(start + 15.0 * i, float(i) * step) for i in range(n)]
    return {"series": {
        "object_plane.arena_used_bytes{node=0}": _series(pts),
        "object_plane.arena_capacity_bytes{node=0}":
            _series([(p[0], float(cap)) for p in pts]),
    }}


def test_growth_warning_fires_on_monotone_fill():
    cfg = _cfg(arena_growth_warn_window_s=120.0,
               arena_growth_warn_min_frac=0.05)
    warns = _arena_growth_warnings(_mono_history(), cfg)
    assert len(warns) == 1
    assert "grew monotonically" in warns[0]
    assert "{node=0}" in warns[0]


def test_growth_warning_quiet_on_dip():
    """One dip anywhere in the window means churn, not a leak."""
    cfg = _cfg(arena_growth_warn_window_s=120.0,
               arena_growth_warn_min_frac=0.05)
    hist = _mono_history()
    key = "object_plane.arena_used_bytes{node=0}"
    pts = hist["series"][key]["points"]
    pts[5] = (pts[5][0], pts[4][1] - 1.0)  # a single free
    assert _arena_growth_warnings(hist, cfg) == []


def test_growth_warning_quiet_below_min_frac():
    """Growth under arena_growth_warn_min_frac of capacity is noise."""
    cfg = _cfg(arena_growth_warn_window_s=120.0,
               arena_growth_warn_min_frac=0.05)
    hist = _mono_history(step=0.001 * (1 << 30))  # ~1% total growth
    assert _arena_growth_warnings(hist, cfg) == []


def test_growth_warning_quiet_on_short_history():
    """< 4 points, or points spanning < half the window, can't be
    judged — a freshly booted node must not warn."""
    cfg = _cfg(arena_growth_warn_window_s=120.0,
               arena_growth_warn_min_frac=0.05)
    assert _arena_growth_warnings(_mono_history(n=3), cfg) == []
    # 10 points squeezed into 9s: plenty of points, tiny span
    pts = [(float(i), float(i) * 1e8) for i in range(10)]
    hist = {"series": {
        "object_plane.arena_used_bytes{node=0}": _series(pts)}}
    assert _arena_growth_warnings(hist, cfg) == []


def test_growth_warning_ignores_other_series():
    cfg = _cfg(arena_growth_warn_window_s=120.0)
    pts = [(15.0 * i, float(i) * 1e9) for i in range(10)]
    hist = {"series": {"object_plane.bytes_pulled{node=0}":
                       _series(pts)}}
    assert _arena_growth_warnings(hist, cfg) == []


def _summary(arena=None, dead=None):
    return {
        "nodes": {0: {"resident_bytes": 100, "resident_objects": 1,
                      "spilled_bytes": 0, "arena": arena or {}}},
        "dead_owner": dead or {"objects": 0, "bytes": 0, "owners": []},
    }


def test_pressure_warning_near_highwater():
    cfg = _cfg(arena_pressure_warn_frac=0.90)
    s = _summary(arena={"capacity": 1000.0, "used_bytes": 950.0})
    warns = _memory_warnings(s, cfg)
    assert len(warns) == 1 and "95% of capacity" in warns[0]
    s = _summary(arena={"capacity": 1000.0, "used_bytes": 800.0})
    assert _memory_warnings(s, cfg) == []


def test_deferred_delete_pileup_warning():
    """Borrow-ledger deferred deletes stuck past the TTL flag a leaked
    zero-copy view (ISSUE 20 satellite)."""
    cfg = _cfg(borrow_deferred_delete_warn_s=30.0)
    s = _summary(arena={"capacity": 1000.0, "used_bytes": 10.0,
                        "deferred_deletes": 3.0,
                        "deferred_delete_oldest_s": 45.0})
    warns = _memory_warnings(s, cfg)
    assert len(warns) == 1
    assert "deferred delete(s) stuck" in warns[0]
    # under the TTL: quiet
    s = _summary(arena={"capacity": 1000.0, "used_bytes": 10.0,
                        "deferred_deletes": 3.0,
                        "deferred_delete_oldest_s": 5.0})
    assert _memory_warnings(s, cfg) == []
    # TTL 0 disables the check entirely
    cfg = _cfg(borrow_deferred_delete_warn_s=0.0)
    s = _summary(arena={"capacity": 1000.0, "used_bytes": 10.0,
                        "deferred_deletes": 3.0,
                        "deferred_delete_oldest_s": 999.0})
    assert _memory_warnings(s, cfg) == []


def test_dead_owner_warning():
    cfg = _cfg()
    s = _summary(dead={"objects": 2, "bytes": 4096,
                       "owners": ["deadbeefcafe", "feedface0000"]})
    warns = _memory_warnings(s, cfg)
    assert len(warns) == 1
    assert "dead worker(s)" in warns[0]
    assert "deadbeef" in warns[0]  # truncated owner hex is listed


# =========================================== r19 satellites (pure units)


class _FakeHead:
    """Stand-in for ctx.head: paged ring readback, or a pre-r19 head
    that only knows the unpaged task_events query."""

    def __init__(self, rows, paged=True, page_size=2):
        self.rows, self.paged, self.page_size = rows, paged, page_size
        self.calls = []

    def call(self, msg, kind, limit, timeout=None):
        self.calls.append(kind)
        if kind.startswith("task_events_page"):
            if not self.paged:
                raise RuntimeError("unknown state query kind")
            cur = int(kind.split(":", 1)[1])
            page = self.rows[cur:cur + self.page_size]
            nxt = cur + len(page)
            return ([{"rows": page, "next": nxt,
                      "done": nxt >= len(self.rows)}],)
        assert kind == "task_events"
        return (list(self.rows),)


def test_pull_task_events_pages_through_ring():
    from ray_tpu.tracing import _pull_task_events

    rows = [{"i": i} for i in range(5)]
    ctx = Namespace(head=_FakeHead(rows, paged=True, page_size=2))
    assert _pull_task_events(ctx) == rows
    assert all(c.startswith("task_events_page") for c in ctx.head.calls)
    assert len(ctx.head.calls) == 3  # ceil(5/2) pages


def test_pull_task_events_falls_back_unpaged():
    """Against a pre-r19 head (no task_events_page kind) the client
    falls back to the single unpaged query — mixed-version clusters
    keep their timelines."""
    from ray_tpu.tracing import _pull_task_events

    rows = [{"i": i} for i in range(5)]
    ctx = Namespace(head=_FakeHead(rows, paged=False))
    assert _pull_task_events(ctx) == rows
    assert ctx.head.calls == ["task_events_page:0", "task_events"]


def test_recorder_glob_matches_arena_series():
    """metrics_history's name filter must reach the new arena gauges:
    `object_plane.arena_*` globs, `object_plane.` prefixes, and the
    exact base name all match tagged series keys."""
    from ray_tpu.core.timeseries import FlightRecorder

    rec = FlightRecorder(1.0, 60.0)
    rows = [{"name": "object_plane.arena_used_bytes", "kind": "gauge",
             "tags": {"node": "0"}, "value": 123.0},
            {"name": "object_plane.arena_capacity_bytes", "kind": "gauge",
             "tags": {"node": "0"}, "value": 1000.0},
            {"name": "tasks.finished", "kind": "gauge", "tags": {},
             "value": 1.0}]
    rec.sample(rows, 1.0)
    rec.sample(rows, 2.0)
    h = rec.history(names=["object_plane.arena_*"])["series"]
    assert set(h) == {"object_plane.arena_used_bytes{node=0}",
                      "object_plane.arena_capacity_bytes{node=0}"}
    assert h["object_plane.arena_used_bytes{node=0}"]["points"][-1][1] \
        == 123.0
    # prefix and exact-base forms reach the same series
    assert "object_plane.arena_used_bytes{node=0}" in \
        rec.history(names=["object_plane."])["series"]
    assert set(rec.history(
        names=["object_plane.arena_used_bytes"])["series"]) == \
        {"object_plane.arena_used_bytes{node=0}"}


# ========================================== live-cluster integration


def _wait_for(pred, timeout=30.0, interval=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(interval)
    return pred()


def test_memory_summary_exact_per_node_bytes(ray_start):
    """The acceptance gate: per-node resident bytes in
    state.memory_summary() agree EXACTLY with the node store's own
    memory_stats() sealed payload bytes, and the job/owner aggregates
    attribute them to this driver."""
    from ray_tpu.core.context import get_context

    ctx = get_context()
    refs = [ray_tpu.put(np.arange(n, dtype=np.float32))
            for n in (1000, 2000, 4000)]
    assert ctx.store.memory_stats()["sealed_count"] >= 3

    def _settled():
        # snapshot BOTH sides inside the loop: a late background seal
        # landing in only one of them must not fail the comparison.
        # sealed_data_bytes is the store-side number under the wire/
        # directory size convention (data frames, no frame-size meta)
        s = state_api.memory_summary()
        m = ctx.store.memory_stats()
        row = (s.get("nodes") or {}).get(0) or {}
        if row.get("resident_bytes") == m["sealed_data_bytes"] and \
                row.get("resident_objects") == m["sealed_count"]:
            return s, m
        return None
    got = _wait_for(_settled)
    assert got, "summary never converged on store ground truth"
    s, stats = got
    exact = stats["sealed_data_bytes"]
    row = s["nodes"][0]
    assert row["resident_bytes"] == exact
    assert row["resident_objects"] == stats["sealed_count"]
    assert s["totals"]["resident_bytes"] == exact
    # job attribution: every byte belongs to this driver's job
    job_hex = ctx.job_id.hex()
    assert s["jobs"][job_hex]["resident_bytes"] == exact
    assert s["jobs"][job_hex]["per_node"][0] == exact
    # owner attribution: the driver is a live owner
    orow = s["owners"][ctx.worker_id]
    assert orow["resident_bytes"] == exact
    assert orow["live"] is True
    assert s["dead_owner"]["bytes"] == 0
    # top objects carry size/holders/age and sort by size desc
    top = s["top_objects"]
    assert len(top) >= 3
    sizes = [o["size"] for o in top]
    assert sizes == sorted(sizes, reverse=True)
    assert all(o["age_s"] >= 0.0 for o in top)
    assert refs  # keep them resident through the asserts


def test_task_results_attributed_to_job(ray_start):
    """Objects sealed on the worker return path carry the job too —
    attribution isn't a driver-put special case."""
    from ray_tpu.core.context import get_context

    @ray_tpu.remote
    def make(n):
        return np.arange(n, dtype=np.float64)

    # big enough to beat max_inline_object_size — inline returns never
    # touch an arena, so they carry no attribution
    refs = [make.remote(100_000) for _ in range(2)]
    ray_tpu.get(refs, timeout=60)
    job_hex = get_context().job_id.hex()

    def _attributed():
        s = state_api.memory_summary()
        j = (s.get("jobs") or {}).get(job_hex) or {}
        return s if j.get("objects", 0) >= 2 else None
    s = _wait_for(_attributed)
    assert s["jobs"][job_hex]["resident_bytes"] > 0
    assert refs


def test_checkpoint_tag_reference_class(ray_start):
    """ctx.tag_objects(..., 'checkpoint') lands in the class breakdown
    — the pipeline's in-memory checkpoints become visible as a class."""
    from ray_tpu.core.context import get_context

    ref = ray_tpu.put(np.arange(8192, dtype=np.uint8))
    get_context().tag_objects([ref], "checkpoint")

    def _tagged():
        s = state_api.memory_summary()
        return s if (s.get("classes") or {}).get("checkpoint_bytes") \
            else None
    s = _wait_for(_tagged)
    assert s["classes"]["checkpoint_bytes"] >= 8192
    tagged = [o for o in s["top_objects"] if o["tag"] == "checkpoint"]
    assert tagged and tagged[0]["object_id"] == ref.id.hex()
    assert ref


def test_arena_gauges_flow_through_timeseries(ray_start):
    """object_plane.arena_used_bytes rides the heartbeat into the r19
    flight recorder: metrics_history's glob returns live per-node
    series (the same path `ray_tpu status` sparklines read)."""
    ray_tpu.put(np.arange(100_000, dtype=np.int64))

    def _recorded():
        hist = state_api.metrics_history(
            names=["object_plane.arena_*"])
        series = hist.get("series", {})
        used = [s for k, s in series.items()
                if k.startswith("object_plane.arena_used_bytes")
                and s["points"]]
        cap = [s for k, s in series.items()
               if k.startswith("object_plane.arena_capacity_bytes")
               and s["points"]]
        return (used, cap) if used and cap else None
    got = _wait_for(_recorded, timeout=45.0)
    assert got, "arena gauges never reached the flight recorder"
    used, cap = got
    assert all(v >= 0 for _, v in used[0]["points"])
    assert cap[0]["points"][-1][1] > 0


def test_list_objects_rows_and_cli_sort(ray_start, capsys, monkeypatch):
    """`ray_tpu list objects` rows grow size/owner/job columns and
    `--sort-by size` orders descending (ISSUE 20 satellite)."""
    from ray_tpu import scripts

    small = ray_tpu.put(np.arange(10, dtype=np.uint8))
    big = ray_tpu.put(np.arange(100_000, dtype=np.uint8))

    def _listed():
        rows = state_api.list_objects(limit=1000)
        return rows if len(rows) >= 2 else None
    rows = _wait_for(_listed)
    for r in rows:
        assert {"size", "owner", "job", "age_s", "tag"} <= set(r)
    monkeypatch.setattr(scripts, "_attached", lambda args: ray_tpu)
    p = scripts.build_parser()
    args = p.parse_args(["list", "objects", "--sort-by", "size"])
    assert args.fn(args) == 0
    out = json.loads(capsys.readouterr().out)
    sizes = [r["size"] for r in out]
    assert sizes == sorted(sizes, reverse=True)
    assert small and big


def test_memory_cli_renders_groups(ray_start, capsys, monkeypatch):
    """`ray_tpu memory` renders totals, the class breakdown, and each
    --group-by view off a live summary."""
    from ray_tpu import scripts

    ref = ray_tpu.put(np.arange(50_000, dtype=np.float32))
    _wait_for(lambda: state_api.memory_summary().get("totals", {})
              .get("resident_bytes") or None)
    monkeypatch.setattr(scripts, "_attached", lambda args: ray_tpu)
    p = scripts.build_parser()
    for group in ("node", "job", "owner"):
        args = p.parse_args(["memory", "--group-by", group])
        assert args.fn(args) == 0
        out = capsys.readouterr().out
        assert "cluster resident:" in out
        assert "by reference class:" in out
        assert f"by {group}:" in out
        assert "top " in out and "object_id" in out
    # --units kb forces fixed units; --sort-by age re-orders; --json
    # dumps the raw summary
    args = p.parse_args(["memory", "--units", "kb", "--sort-by", "age"])
    assert args.fn(args) == 0
    out = capsys.readouterr().out
    assert "KB" in out and "(by age)" in out
    args = p.parse_args(["memory", "--json"])
    assert args.fn(args) == 0
    s = json.loads(capsys.readouterr().out)
    assert {"nodes", "jobs", "owners", "classes", "totals"} <= set(s)
    assert ref


def test_api_summary_memory_endpoint(ray_start):
    """/api/summary/memory serves the same aggregates over HTTP (the
    doctor smokes it with every other endpoint)."""
    from ray_tpu.dashboard import start_dashboard

    ref = ray_tpu.put(np.arange(4096, dtype=np.uint8))
    _wait_for(lambda: state_api.memory_summary().get("totals", {})
              .get("resident_bytes") or None)
    dash = start_dashboard(port=0)
    try:
        with urllib.request.urlopen(dash.url + "/api/summary/memory",
                                    timeout=30) as r:
            body = json.loads(r.read())
        assert {"nodes", "jobs", "owners", "classes", "dead_owner",
                "top_objects", "totals"} <= set(body)
        assert body["totals"]["resident_bytes"] > 0
    finally:
        dash.stop()
    assert ref


def test_a_killed_nodes_arena_is_gone_after_cluster_shutdown():
    """A host test that FAILS mid-way never reaches its own
    `handle.terminate()`: the agent it killed (SIGKILL: no exit path runs,
    so the agent cannot unlink its arena) and an agent it left running are
    both ended by `Cluster.shutdown`, and neither leaves its /dev/shm
    arena behind to pin its size until someone sweeps it."""
    import os

    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 1, "num_tpus": 0})
    try:
        killed = cluster.add_remote_node(num_cpus=1)
        left = cluster.add_remote_node(num_cpus=1)
        paths = [f"/dev/shm/{h.store_name}" for h in (killed, left)]
        assert all(h.store_name for h in (killed, left))
        assert all(os.path.exists(p) for p in paths)
        killed.proc.kill()
        killed.proc.wait(timeout=10)
        assert os.path.exists(paths[0])     # nobody has unlinked it yet
    finally:
        cluster.shutdown()
    assert not any(os.path.exists(p) for p in paths)
    assert left.proc.poll() is not None

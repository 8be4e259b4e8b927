"""The process's compile ledger (`ray_tpu.utils.compile_cache`, PR 58), on
the CPU: a program the backend compiled is told from one the persistent
cache held, the totals add up at every read, the log names the program,
the listeners are installed once, and every live engine's `stats` holds
the process's totals without registering anything that outlives it."""

import gc
import signal
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.config import tiny_config
from ray_tpu.models.engine import InferenceEngine
from ray_tpu.models.transformer import init_params
from ray_tpu.utils import compile_cache
from ray_tpu.utils.compile_cache import compile_ledger, compile_log

KEYS = ("compile_requests", "compile_wait_s", "programs_loaded",
        "programs_compiled", "cache_load_s", "trace_lower_s")


@pytest.fixture(autouse=True)
def time_limit():
    """Each test's own: a compile that hangs fails that test alone."""
    def late(*_):
        raise TimeoutError("over this test's 120 s")
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    was = signal.signal(signal.SIGALRM, late)
    signal.alarm(120)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, was)


@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    """An empty persistent cache that keeps every program; the session's
    own place and threshold are put back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() is None   # placed outside
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        jax.config.update("jax_compilation_cache_dir", keep[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          keep[1])
        cc.reset_cache()


def _adds_up(ledger):
    assert set(ledger) == set(KEYS)
    assert ledger["compile_requests"] == \
        ledger["programs_compiled"] + ledger["programs_loaded"]
    assert all(type(v) in (int, float) and v >= 0 for v in ledger.values())
    assert ledger["cache_load_s"] <= ledger["compile_wait_s"]
    return ledger


def _since(before):
    now = _adds_up(compile_ledger())
    return {k: now[k] - before[k] for k in KEYS}


def test_a_new_program_is_compiled_and_the_same_one_again_is_loaded(
        fresh_cache):
    def ledger_probe_a(x):
        return jnp.tanh(x @ x.T).sum(axis=0) * 3.0

    x = jnp.ones((7, 13), jnp.float32)
    jax.block_until_ready(x)
    before = _adds_up(compile_ledger())
    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(ledger_probe_a)(x))
    wall = time.perf_counter() - t0
    cold = _since(before)
    assert cold["compile_requests"] == cold["programs_compiled"] == 1
    assert cold["programs_loaded"] == 0 and cold["cache_load_s"] == 0
    assert cold["compile_wait_s"] > 0 and cold["trace_lower_s"] > 0
    # seconds of one thread, an outermost trace alone: `jnp.tanh` and the
    # rest are jitted themselves and traced INSIDE the probe's trace
    assert cold["compile_wait_s"] + cold["trace_lower_s"] <= wall
    entry = compile_log(last=1)[0]
    assert entry["fun_name"] == "jit(ledger_probe_a)"   # as JAX names it
    assert entry["loaded"] is False
    assert entry["wall_s"] == pytest.approx(cold["compile_wait_s"])
    assert abs(entry["t_unix"] - time.time()) < 60
    assert list(fresh_cache.iterdir())     # the program was kept

    jax.clear_caches()     # the process forgets; the directory does not
    before = compile_ledger()
    jax.block_until_ready(jax.jit(ledger_probe_a)(x))
    warm = _since(before)
    assert warm["compile_requests"] == warm["programs_loaded"] == 1
    assert warm["programs_compiled"] == 0
    assert 0 < warm["cache_load_s"] <= warm["compile_wait_s"]
    entry = compile_log(last=1)[0]
    assert entry["fun_name"] == "jit(ledger_probe_a)" and entry["loaded"]

    # and once more in the same process: no request at all
    before = compile_ledger()
    jax.block_until_ready(jax.jit(ledger_probe_a)(x))
    assert _since(before)["compile_requests"] == 0


def test_the_totals_add_up_at_every_read_while_programs_compile(fresh_cache):
    stop, reads, bad = threading.Event(), [], []

    def reader():
        while not stop.is_set():
            ledger = compile_ledger()
            reads.append(ledger["compile_requests"])
            if ledger["compile_requests"] != ledger["programs_compiled"] \
                    + ledger["programs_loaded"]:
                bad.append(ledger)

    def probe():
        # a new function object of the same text each call: the process's
        # own cache misses it, the directory holds its program (as after
        # `jax.clear_caches()`, without emptying this worker's session)
        def ledger_probe_b(x):
            return jnp.cumsum(x * 2.0, axis=0) - x
        return jax.jit(ledger_probe_b)

    xs = jax.block_until_ready([jnp.ones((n, 17)) for n in range(3, 9)])
    thread = threading.Thread(target=reader)
    thread.start()
    before = compile_ledger()
    try:
        first = probe()
        for x in xs:              # a new shape each: six requests
            jax.block_until_ready(first(x))
        again = probe()
        for x in xs:              # the same six, from the directory
            jax.block_until_ready(again(x))
    finally:
        stop.set()
        thread.join(60)
    assert not bad and len(reads) > 10
    got = _since(before)
    probes = [e for e in compile_log() if e["fun_name"]
              == "jit(ledger_probe_b)"][-12:]
    assert [e["loaded"] for e in probes] == [False] * 6 + [True] * 6
    assert got["programs_compiled"] == got["programs_loaded"] == 6


def test_installing_twice_counts_once():
    from jax._src import monitoring

    compile_cache.install_compile_ledger()
    compile_cache.enable_compile_cache()
    compile_cache.install_compile_ledger()
    for mine, theirs in (
            (compile_cache._on_duration,
             monitoring.get_event_duration_listeners()),
            (compile_cache._on_event, monitoring.get_event_listeners()),
            (compile_cache._on_start, monitoring.get_scalar_listeners())):
        assert theirs.count(mine) == 1

    def ledger_probe_c(x):
        return x[::-1] + 5

    x = jax.block_until_ready(jnp.arange(11))
    before = compile_ledger()
    jax.block_until_ready(jax.jit(ledger_probe_c)(x))
    assert _since(before)["compile_requests"] == 1
    assert len(compile_log()) <= 256 and len(compile_log(last=2)) == 2
    copy = compile_ledger()
    copy["compile_requests"] = -1          # a copy: the ledger keeps its own
    assert compile_ledger()["compile_requests"] >= 1


def test_every_live_engine_reads_the_process_and_a_dropped_one_is_gone():
    cfg = tiny_config()
    params = init_params(jax.random.key(0), cfg)
    gc.collect()
    registered = len(compile_cache._followers)
    shape = dict(slots=2, max_prompt_len=16, max_new_tokens=4)
    one = InferenceEngine(params, cfg, **shape)
    two = InferenceEngine(params, cfg, **shape)
    assert len(compile_cache._followers) == registered + 2
    # the process's numbers from construction: what compiled before either
    # engine existed is in them
    assert {k: one.stats[k] for k in KEYS} == compile_ledger()
    asked = one.stats["compile_requests"]
    assert asked > 0

    def ledger_probe_d(x):
        return jnp.sqrt(x + 2.0)

    x, y = jax.block_until_ready((jnp.ones((5, 3)), jnp.ones((6, 3))))
    asked = one.stats["compile_requests"]
    jax.block_until_ready(jax.jit(ledger_probe_d)(x))
    # kept live by the listeners: nobody copied anything at this read
    assert one.stats["compile_requests"] == asked + 1
    for eng in (one, two):
        assert {k: eng.stats[k] for k in KEYS} == compile_ledger()
        assert all(type(eng.stats[k]) in (int, float) for k in KEYS)
    stats = two.stats
    del two, eng
    gc.collect()
    assert len(compile_cache._followers) == registered + 1
    jax.block_until_ready(jax.jit(ledger_probe_d)(y))
    assert stats["compile_requests"] == asked + 1   # left where it stood
    assert one.stats["compile_requests"] == asked + 2
    del one
    gc.collect()
    assert len(compile_cache._followers) == registered

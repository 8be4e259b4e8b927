"""The load generator: the same work for every seed, in another order."""

import math
from collections import Counter

import pytest

import bench_paths  # noqa: F401  (puts the checkout root on sys.path)
from benchmark.harness import spec, traffic

SEEDS = [0, 1, 7, 2 ** 31 + 11, 2 ** 33 + 5]
CHAT = spec.load_traffic("chat-steady")
BATCH = spec.load_traffic("batch-closed")


def _multiset(reqs, key):
    return Counter(r[key] for r in reqs)


def _part(seed, phase, window_s=51.0):
    sched = traffic.open_loop_schedule(CHAT, seed, window_s)
    return [r for r in sched["requests"] if {
        "counted": r["counted"], "ramp": r["due_s"] < 0,
        "tail": r["due_s"] >= window_s}[phase]]


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_every_seed_offers_the_same_length_multiset(seed):
    a, b = _part(SEEDS[0], "counted"), _part(seed, "counted")
    assert len(a) == len(b) == round(CHAT["rate_per_s"] * 51.0)
    assert _multiset(a, "prompt_len") == _multiset(b, "prompt_len")
    assert _multiset(a, "output_len") == _multiset(b, "output_len")
    want_p = traffic.stratified_lognormal(len(a), **{
        k: CHAT["prompt_len"][k] for k in ("median", "sigma")},
        lo=CHAT["prompt_len"]["min"], hi=CHAT["prompt_len"]["max"])
    assert sorted(r["prompt_len"] for r in a) == want_p


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("phase", ["ramp", "tail"])
def test_ramp_and_tail_are_stretches_of_the_same_process(seed, phase):
    """The same rate before and after the window: a fixed count, its own
    stratified lengths (the same multiset for every seed), none counted."""
    part, length = _part(seed, phase), CHAT[f"{phase}_s"]
    n = round(CHAT["rate_per_s"] * length)
    assert len(part) == n and not any(r["counted"] for r in part)
    p = CHAT["prompt_len"]
    assert sorted(r["prompt_len"] for r in part) == \
        traffic.stratified_lognormal(n, p["median"], p["sigma"], p["min"],
                                     p["max"])
    lo = -CHAT["ramp_s"] if phase == "ramp" else 51.0
    assert all(lo <= r["due_s"] < lo + length for r in part)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("window_s", [10.0, 51.0])
def test_exactly_n_arrivals_fall_in_the_window(seed, window_s):
    sched = traffic.open_loop_schedule(CHAT, seed, window_s)
    n = round(CHAT["rate_per_s"] * window_s)
    inside = [r for r in sched["requests"] if 0 <= r["due_s"] < window_s]
    assert sched["n_counted"] == n == len(inside)
    assert all(r["counted"] for r in inside)
    assert not any(r["counted"] for r in sched["requests"]
                   if r not in inside)
    dues = [r["due_s"] for r in sched["requests"]]
    assert dues == sorted(dues)
    assert -CHAT["ramp_s"] <= dues[0] < -CHAT["ramp_s"] + 3.0
    assert window_s + CHAT["tail_s"] - 3.0 < dues[-1] < \
        window_s + CHAT["tail_s"]


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_another_seed_gives_another_order_and_other_times(seed):
    a = traffic.open_loop_schedule(CHAT, SEEDS[0], 51.0)["requests"]
    b = traffic.open_loop_schedule(CHAT, seed, 51.0)["requests"]
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    assert [r["token_seed"] for r in a] != [r["token_seed"] for r in b]
    # ... and another pairing of prompts with outputs
    ka = sorted((r["prompt_len"], r["output_len"]) for r in a if r["counted"])
    kb = sorted((r["prompt_len"], r["output_len"]) for r in b if r["counted"])
    assert ka != kb


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_the_same_seed_gives_the_same_run(seed):
    assert traffic.open_loop_schedule(CHAT, seed, 20.0) == \
        traffic.open_loop_schedule(CHAT, seed, 20.0)
    assert traffic.closed_loop_schedule(BATCH, seed) == \
        traffic.closed_loop_schedule(BATCH, seed)
    assert traffic.prompt_tokens(seed, 9, 100) == \
        traffic.prompt_tokens(seed, 9, 100)


@pytest.mark.parametrize("n,median,sigma,lo,hi", [
    (230, 320, 0.9, 32, 1024), (230, 96, 0.7, 16, 256), (9, 50, 0.5, 1, 99)])
def test_stratified_lognormal_is_the_distribution_it_names(n, median, sigma,
                                                           lo, hi):
    xs = traffic.stratified_lognormal(n, median, sigma, lo, hi)
    assert len(xs) == n and xs == sorted(xs)
    assert all(lo <= x <= hi for x in xs)
    assert abs(sorted(xs)[n // 2] - median) <= max(2, 0.03 * median)
    # about 16% of a log-normal lies below median * exp(-sigma)
    below = sum(x < median * math.exp(-sigma) for x in xs) / n
    assert 0.10 <= below <= 0.22


@pytest.mark.parametrize("seed", SEEDS)
def test_arrivals_are_uniform_draws_over_the_window(seed):
    """Order statistics of n uniform draws (a Poisson process given its
    count): every third of the window gets about a third of them, and the
    gaps look exponential (their median is ln 2 of their mean)."""
    due = [r["due_s"] for r in _part(seed, "counted")]
    n = len(due)
    for k in range(3):
        share = sum(17.0 * k <= d < 17.0 * (k + 1) for d in due) / n
        assert abs(share - 1 / 3) < 0.1, (k, share)
    gaps = sorted(y - x for x, y in zip(due, due[1:]))
    mean = sum(gaps) / len(gaps)
    assert gaps[len(gaps) // 2] / mean == pytest.approx(math.log(2),
                                                        abs=0.2)


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_pool_is_the_chat_multiset(seed):
    pool = traffic.closed_loop_schedule(BATCH, seed)["pool"]
    chat = [r for r in traffic.open_loop_schedule(
        CHAT, 0, 51.0)["requests"] if r["counted"]]
    assert len(pool) == BATCH["pool"] == len(chat)
    assert _multiset(pool, "prompt_len") == _multiset(chat, "prompt_len")
    assert _multiset(pool, "output_len") == _multiset(chat, "output_len")


@pytest.mark.parametrize("seed", SEEDS)
def test_prompt_tokens_stay_in_the_vocabulary(seed):
    toks = traffic.prompt_tokens(seed, 300, 92544)
    assert len(toks) == 300 and all(1 <= t < 92544 for t in toks)


@pytest.mark.parametrize("seed,step", [(0, 0), (0, 1), (2 ** 33, 5)])
def test_train_batches_differ_by_step_and_repeat_by_seed(seed, step):
    a = traffic.train_batch_seed(seed, step)
    assert a == traffic.train_batch_seed(seed, step)
    assert a != traffic.train_batch_seed(seed, step + 1)
    assert 0 <= a < 2 ** 63


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("key", ["prompt_len", "output_len"])
def test_the_seed_orders_each_multiset_on_its_own(seed, key):
    """A permutation, not a sort: neither ascending nor descending, and
    not the order the other multiset got."""
    reqs = _part(seed, "counted")
    values = [r[key] for r in reqs]
    assert values != sorted(values) and values != sorted(values)[::-1]
    other = "output_len" if key == "prompt_len" else "prompt_len"
    rank = lambda xs: sorted(range(len(xs)), key=xs.__getitem__)  # noqa: E731
    assert rank(values) != rank([r[other] for r in reqs])


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_closed_loop_pool_differs_by_seed_in_order_only(seed):
    a = traffic.closed_loop_schedule(BATCH, seed)["pool"]
    b = traffic.closed_loop_schedule(BATCH, seed + 1)["pool"]
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    assert _multiset(a, "prompt_len") == _multiset(b, "prompt_len")
    assert _multiset(a, "output_len") == _multiset(b, "output_len")

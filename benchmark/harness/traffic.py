"""The one general load generator: turns a traffic file's parameters and
`--seed` into the requests of a run. A new traffic mix is a new data file.

The LENGTHS of a run are the evenly spaced quantiles of the file's
distributions: every seed offers exactly the same multiset of work. The
seed permutes them (prompts and outputs each on their own, which pairs
them anew), draws every request's token ids, and draws the ARRIVAL TIMES:
a fixed number of arrivals for the window, at times that are the order
statistics of that many uniform draws over it (a Poisson process, given
its count), the same process at the same rate through the ramp before the
window and the tail after it. Plain Python `random.Random(seed)`: any
whole-number seed is fine.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import List


def stratified_lognormal(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> List[int]:
    """The n evenly spaced quantiles ((i + 0.5) / n) of a log-normal with
    the given median and sigma, rounded and clipped to [lo, hi]."""
    if n <= 0:
        return []
    nd = NormalDist()
    mu = math.log(median)
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(hi, max(lo, round(x)))))
    return out


def _lengths(params: dict, n: int, rnd: random.Random):
    """n (prompt, output) lengths: the two stratified multisets, each in
    an order of the seed's own."""
    p, o = params["prompt_len"], params["output_len"]
    prompts = stratified_lognormal(n, p["median"], p["sigma"], p["min"],
                                   p["max"])
    outputs = stratified_lognormal(n, o["median"], o["sigma"], o["min"],
                                   o["max"])
    rnd.shuffle(prompts)
    rnd.shuffle(outputs)
    return prompts, outputs


def _stretch(params: dict, rate: float, start_s: float, length_s: float,
             counted: bool, rnd: random.Random) -> List[dict]:
    """round(rate x length_s) requests due in [start_s, start_s +
    length_s): stratified lengths in the seed's order, at the sorted
    uniform draws of the seed."""
    n = max(0, round(rate * length_s))
    prompts, outputs = _lengths(params, n, rnd)
    dues = sorted(start_s + rnd.random() * length_s for _ in range(n))
    return [{"due_s": dues[i], "prompt_len": prompts[i],
             "output_len": outputs[i], "counted": counted,
             "token_seed": rnd.getrandbits(32)} for i in range(n)]


def open_loop_schedule(params: dict, seed: int, window_s: float) -> dict:
    """Requests of an open-loop run. Times are seconds from the opening of
    the measured window: the ramp before it has negative times, the tail
    after it times >= window_s. Exactly round(rate * window_s) requests
    are due inside [0, window_s): they are the counted ones, and their
    lengths are the same multiset for every seed. The ramp and the tail
    are stretches of the same process (their own stratified lengths, their
    own fixed counts), so the window opens and closes on a loaded
    engine."""
    rnd = random.Random(seed)
    rate = params["rate_per_s"]
    ramp_s, tail_s = params["ramp_s"], params["tail_s"]
    window = _stretch(params, rate, 0.0, window_s, True, rnd)
    ramp = _stretch(params, rate, -ramp_s, ramp_s, False, rnd)
    tail = _stretch(params, rate, window_s, tail_s, False, rnd)
    return {"requests": ramp + window + tail, "n_counted": len(window),
            "window_s": window_s, "ramp_s": ramp_s, "tail_s": tail_s,
            "rate_per_s": rate}


def closed_loop_schedule(params: dict, seed: int) -> dict:
    """A closed loop's work list: ``params['pool']`` stratified (prompt,
    output) lengths in the seed's order; each of ``clients`` takes the
    next entry when its last request returns, and the list is walked
    round and round."""
    rnd = random.Random(seed)
    prompts, outputs = _lengths(params, params["pool"], rnd)
    pool = [{"prompt_len": p, "output_len": o,
             "token_seed": rnd.getrandbits(32)}
            for p, o in zip(prompts, outputs)]
    return {"pool": pool, "clients": params["clients"],
            "ramp_s": params["ramp_s"]}


def prompt_tokens(token_seed: int, n: int, vocab: int) -> List[int]:
    """n token ids in [1, vocab) from the request's own seed."""
    rnd = random.Random(token_seed)
    return [rnd.randrange(1, vocab) for _ in range(n)]


def train_batch_seed(seed: int, step: int) -> int:
    """Seed of the batch of ``step``: fresh data every step, the same data
    for the same (seed, step)."""
    return (seed * 1_000_003 + step) % (2 ** 63)

"""The one general traffic generator. A mix is a data file under
`chipbench/traffic/`; this module turns its parameters and `--seed` into
requests or batches. It imports nothing of the program.

The SCHEDULE of a mix (which sizes, in which order, at which gaps) is the
mix's own: it is drawn once from the quantiles of the stated distributions
with the mix's `order_seed`, as a recorded trace would be, and is the same
in every run. `--seed` makes the token ids (and, in the loops, the weights).
So every run of a cell holds the same work at the same times; with the
order drawn from `--seed`, which long request met which window edge moved
the chat cell's tokens/s by 7% between seeds (PERF.md, PR 24).
"""
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _norm_ppf(u):
    # Acklam's rational approximation of the normal quantile (|err| < 1.2e-9)
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    u = np.asarray(u, np.float64)
    out = np.empty_like(u)
    lo, hi = u < 0.02425, u > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(u[lo]))
    out[lo] = (((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) / ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1)
    q = np.sqrt(-2 * np.log(1 - u[hi]))
    out[hi] = -(((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) / ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1)
    q = u[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0]*r+a[1])*r+a[2])*r+a[3])*r+a[4])*r+a[5])*q / (((((b[0]*r+b[1])*r+b[2])*r+b[3])*r+b[4])*r+1)
    return out


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of a length distribution, clipped, as ints."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * _norm_ppf(u))
    elif kind == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * u
    elif kind == "fixed":
        x = np.full(n, dist["value"], np.float64)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist.get("min", 1), dist.get("max", 1 << 30)).astype(np.int64)


def _phase(rng, n, rate, start, duration, p_q, o_q):
    """n arrivals inside [start, start + duration): the n quantiles of the
    exponential gap in seeded order, stretched so that the phase holds them
    all; sizes are the n quantiles of each length distribution, in seeded
    orders of their own."""
    u = (np.arange(n) + 0.5) / n
    gaps = (-np.log(1 - u) / rate)[rng.permutation(n)]
    ends = np.cumsum(gaps)
    due = start + (ends - 0.5 * gaps) * (duration / ends[-1])
    return due, p_q[rng.permutation(n)], o_q[rng.permutation(n)]


def requests(mix: dict, seed: int, seconds: float, vocab: int):
    """[(due_s or None, prompt ids, max_new_tokens)], in sending order.

    Open loop: two phases, the ramp before the window and the window. Each
    holds rate x duration arrivals whose gaps are the quantiles of the
    exponential at the mix's rate and whose lengths are the quantiles of
    the stated distributions, in the order the mix's `order_seed` draws.
    Closed loop: due is None (sent when the last one ends); sizes come in
    cycles of `cycle` requests, each cycle the same quantiles, paired and
    ordered by `order_seed`."""
    rng = np.random.RandomState(int(mix["order_seed"]))     # the schedule: the mix's own
    ids_rng = np.random.RandomState(seed % (2 ** 32))       # the tokens: the run's
    due, plens, olens = [], [], []
    if mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        start = 0.0
        for duration in (float(mix.get("ramp_s", 0.0)), float(seconds)):
            n = int(round(rate * duration))
            if n < 1:
                start += duration
                continue
            d, p, o = _phase(rng, n, rate, start, duration,
                             quantiles(mix["prompt_len"], n), quantiles(mix["output_len"], n))
            due.extend(d), plens.extend(p), olens.extend(o)
            start += duration
    else:
        cycle = int(mix["cycle"])
        n = int(math.ceil(seconds / float(mix["least_request_s"]) / cycle)) * cycle
        p_q = quantiles(mix["prompt_len"], cycle)
        o_q = quantiles(mix["output_len"], cycle)
        for _ in range(n // cycle):
            plens.extend(p_q[rng.permutation(cycle)])
            olens.extend(o_q[rng.permutation(cycle)])
        due = [None] * n
    out = []
    for i in range(len(plens)):
        ids = ids_rng.randint(1, vocab, size=int(plens[i])).tolist()
        out.append((None if due[i] is None else float(due[i]), ids, int(olens[i])))
    return out


def train_batch(mix: dict, seed: int, vocab: int, replicas: int = 1):
    """One fixed batch of token ids and labels, rows all different: ids in
    [1, vocab) (0 is the padding row), every position labelled."""
    rng = np.random.RandomState(seed % (2 ** 32))
    shape = (int(mix["batch_per_replica"]) * replicas, int(mix["seq"]))
    ids = rng.randint(1, vocab, shape).astype(np.int64)
    labels = rng.randint(0, vocab, shape).astype(np.int64)
    return ids, labels

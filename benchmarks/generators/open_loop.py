"""Open-loop arrivals: requests are due on a schedule whether or not
earlier ones have finished (independent users).

Parameters (the traffic file): ``rate_per_s``, ``ramp_s``, ``drain_s``,
``ttft_ceiling_s``, ``prompt_len`` and ``output_len`` (distributions,
see dist.py). Arrival gaps are exponential (Poisson arrivals). The ramp
fills the system before the window opens and is part of set-up; requests
due at t >= 0 are the window's.

A generator owns its loop discipline: ``build`` makes the requests from
the seed, ``drive`` offers them to a client (the plane: ``record``,
``submit``, ``join_submitters``) around the window, and ``account`` says
which of them were attempted and which failed. A new discipline is a new
file like this one.
"""
import threading
import time

import numpy as np

from benchmarks.generators import dist


def _times(n, span, rng):
    """n arrival offsets in [0, span): the first at 0, then the n - 1
    stratified exponential gaps in seeded order, scaled so that the mean
    gap is span / n."""
    if n <= 1:
        return np.zeros((n,))
    gaps = np.asarray(dist.stratified({"dist": "exponential", "mean": 1.0},
                                      n - 1))
    rng.shuffle(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps)])
    return t * (span * (n - 1) / n / gaps.sum())


def _part(n, t0, span, params, vocab_limit, rng, phase):
    times = _times(n, span, rng)
    plen = np.asarray(dist.stratified_ints(params["prompt_len"], n))
    olen = np.asarray(dist.stratified_ints(params["output_len"], n))
    rng.shuffle(plen)
    rng.shuffle(olen)
    return [{"due": float(t0 + times[i]), "phase": phase,
             "prompt": rng.integers(0, vocab_limit, size=int(plen[i]),
                                    dtype=np.int64),
             "max_new": int(olen[i])} for i in range(n)]


def build(params, seed, seconds, vocab_limit):
    """The run's requests in due order; ``due`` is seconds relative to
    the opening of the window."""
    rng = np.random.default_rng(abs(int(seed)))
    rate, ramp = params["rate_per_s"], params["ramp_s"]
    out = _part(int(round(rate * ramp)), -ramp, ramp, params,
                vocab_limit, rng, "ramp")
    out += _part(int(round(rate * seconds)), 0.0, seconds, params,
                 vocab_limit, rng, "window")
    return out


def drive(client, specs, params, seconds, opened, closed):
    """Offer every request at its due time, each from a thread of its
    own (``client.submit``), so that a slow submit() delays that request
    and not this clock; then wait up to ``drain_s`` for the stragglers."""
    stop = threading.Event()
    t_open = time.perf_counter() + float(params["ramp_s"]) + 0.05
    recs = [client.record(s, t_open + s["due"]) for s in specs]

    def generate():
        for rec in recs:
            while True:
                wait = rec.due - time.perf_counter()
                if wait <= 0 or stop.is_set():
                    break
                time.sleep(min(wait, 0.05))
            if stop.is_set():
                return
            client.submit(rec)

    thread = threading.Thread(target=generate, name="bench-generator",
                              daemon=True)
    thread.start()
    time.sleep(max(0.0, t_open - time.perf_counter()))
    opened()
    time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    t_close = time.perf_counter()
    closed()
    thread.join(timeout=60.0)
    stop.set()
    client.join_submitters(60.0)
    deadline = time.perf_counter() + float(params["drain_s"])
    while time.perf_counter() < deadline and not all(
            r.finished or r.error for r in recs):
        time.sleep(0.05)
    if thread.is_alive():
        raise RuntimeError("the load generator did not stop")
    return {"recs": recs, "t_open": t_open, "t_close": t_close}


def account(run, params):
    """Attempted: the requests due in the window. Failed: one that was
    refused, shed, cut short, unfinished by the drain limit, or whose
    first token came later than ``ttft_ceiling_s`` after it was due."""
    ceiling = float(params["ttft_ceiling_s"])
    mine = [r for r in run["recs"] if r.phase == "window"]
    failed = [r for r in mine if not r.finished
              or r.stamps[0] - r.due > ceiling]
    return {"attempted": mine, "failed": failed, "checks": []}

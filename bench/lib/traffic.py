"""The one traffic generator. A mix is a data file
(``bench/traffic/<name>.json``); this module reads its parameters and
nothing else decides what is sent.

Open loop (``"loop": "open"``): arrivals are drawn from the seed alone,
as Poisson arrivals at ``rate_qps``; each arrival names the next query
of a seeded permutation of the pool, cycled. Every request is timed
from when it was due, not from when it was sent, so a stalled sender
shows as latency.

Closed loop (``"loop": "closed"``): one caller sends the whole pool in
each call and sends the next call when the last returns.
"""
from __future__ import annotations

import time

import numpy as np

ARRIVALS_STREAM = 11     # seed stream of the schedule (data uses 0 and 1)


def open_loop_schedule(seed: int, mix: dict, seconds: float,
                       pool: int) -> tuple[np.ndarray, np.ndarray]:
    """(due seconds from the start of the window [n], query index [n])
    for every request due in ``[0, seconds)``. Depends on ``seed``,
    ``mix``, ``seconds`` and ``pool`` only."""
    rng = np.random.default_rng([seed, ARRIVALS_STREAM])
    n = rng.poisson(float(mix["rate_qps"]) * seconds)
    due = np.sort(rng.uniform(0.0, seconds, n))
    order = rng.permutation(pool)
    qidx = order[np.arange(due.size) % pool]
    return due, qidx.astype(np.int64)


def run_open_loop(submit, coords: np.ndarray, vals: np.ndarray,
                  due: np.ndarray, qidx: np.ndarray, *,
                  clock=time.monotonic, annotate=None):
    """Send request ``i`` (query ``qidx[i]``) at ``start + due[i]``
    through ``submit(coords, vals) -> future``. Returns (start,
    send times [n], futures [n]): the caller reads each future's
    completion against ``start + due``."""
    n = due.size
    sent = np.empty(n)
    futs = [None] * n
    start = clock() + 0.01
    for i in range(n):
        lag = start + due[i] - clock()
        if lag > 0:
            time.sleep(lag)
        t = clock()
        if annotate is not None:
            with annotate("bench.submit"):
                futs[i] = submit(coords[qidx[i]], vals[qidx[i]])
        else:
            futs[i] = submit(coords[qidx[i]], vals[qidx[i]])
        sent[i] = t
    return start, sent, futs


def run_closed_loop(search, seconds: float, *, clock=time.perf_counter,
                    annotate=None):
    """Call ``search()`` until ``seconds`` have passed; returns (window
    seconds, list of results). The window ends when the last call
    returns, so every query counted finished inside it."""
    out = []
    start = clock()
    while clock() - start < seconds:
        if annotate is not None:
            with annotate("bench.search"):
                out.append(search())
        else:
            out.append(search())
    return clock() - start, out

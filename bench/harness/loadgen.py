"""Open-loop load: requests due at fixed times, submitted by one thread
whether or not earlier ones are answered.

After `repro.serve.loadgen.open_loop`, with two changes: each request is
timed from when it was due, not from when the submitter got to it, so a
stalled submitter cannot hide queueing; and the submitter's lateness is
recorded.  The number of requests is fixed by the rate and the window:
N arrival times, uniform over the window and sorted, are a Poisson
process conditioned on N arrivals.  The arrival times come from the
traffic file's own seed and only the roots from the run's, so every
seed offers the same arrivals with other roots.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from bench.harness import trace


@dataclasses.dataclass
class Sent:
    root: int
    due: float            # host clock
    submitted: float
    request: object       # the program's request handle


def schedule(arrival_seed: int, seed: int, rate: float, seconds: float,
             n_roots: int) -> tuple:
    """(due offsets in seconds, roots): round(rate x seconds) requests at
    times drawn from `arrival_seed`, roots uniform over `n_roots` drawn
    from `seed`."""
    n = max(1, int(round(rate * seconds)))
    offsets = np.sort(np.random.default_rng(arrival_seed).uniform(
        0.0, seconds, n))
    return offsets, np.random.default_rng(seed).integers(0, n_roots, n)


def open_loop(submit, offsets, roots, start: float) -> list:
    """Submits ``roots[i]`` at ``start + offsets[i]`` from one thread and
    returns every request sent, once the last is submitted."""
    sent: list = []

    def submitter():
        with trace.span("submitter"):
            for off, root in zip(offsets, roots):
                due = start + float(off)
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                req = submit(int(root))
                sent.append(Sent(int(root), due, time.perf_counter(), req))

    thread = threading.Thread(target=submitter, name="bench-submitter",
                              daemon=True)
    thread.start()
    thread.join()
    return sent


def harvest(sent: list, deadline: float) -> tuple:
    """(latency in seconds from due time, answered) per request, waiting
    until `deadline` at the latest.  A request not answered by then, or
    answered with an error, counts with its wait so far, which is longer
    than any answered one's."""
    latency, ok = [], []
    with trace.span("harvest"):
        for s in sent:
            try:
                s.request.result(max(deadline - time.perf_counter(), 0.0))
                latency.append(s.request.done_at - s.due)
                ok.append(True)
            except Exception:  # noqa: BLE001 — any failure is a data point
                latency.append(max(time.perf_counter(), deadline) - s.due)
                ok.append(False)
    return np.asarray(latency), np.asarray(ok)

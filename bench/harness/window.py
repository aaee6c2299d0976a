"""The measured window: a closed, a routed or an open loop over one server.

Every request is timed on the host's monotonic clock.  A request
completes when its decoded result is ready on the device
(``block_until_ready``).  Host spans (``bench.submit``, ``bench.flush``,
``bench.wait``, ``bench.sleep``) go through :class:`Spans`, which keeps
their durations and, in a traced run, writes them into the profiler's
trace on the device trace's clock.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Callable, Dict, List

import numpy as np

from . import traffic as tr


class Spans:
    """Host spans of the benchmark: durations kept, traced on demand."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.seconds: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation("bench." + name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t0)


def _ready(y) -> None:
    import jax

    jax.block_until_ready(y)


def closed_loop(server, traffic: dict, layers: int, operands: Callable,
                seconds: float, spans: Spans, keep: Callable
                ) -> List[tr.Request]:
    """One client: send, wait for the result, send the next.  Sends until
    ``seconds`` have passed, then completes the product in flight.
    ``keep(req, result)`` decides which results stay for the check."""
    reqs: List[tr.Request] = []
    t0 = time.perf_counter()
    while not reqs or time.perf_counter() - t0 < seconds:
        req = tr.closed_request(traffic, len(reqs), layers)
        a, b = operands(req)
        req.sent_s = time.perf_counter() - t0
        with spans("submit"):
            rid = server.submit(a, b)
        with spans("flush"):
            out, failures = server.flush()
        if rid in out:
            with spans("wait"):
                _ready(out[rid])
            req.done_s = time.perf_counter() - t0
            keep(req, out[rid])
        else:
            req.failure = failures.get(rid, "no result")
        reqs.append(req)
    return reqs


def routed_loop(server, traffic: dict, layers: int, routings, seed: int,
                operands: Callable, seconds: float, spans: Spans,
                keep: Callable) -> List[tr.Request]:
    """One client over a routed layer: each request submits one product a
    held expert (``operands(req)`` gives their operand pairs), flushes
    once, and completes when the last product is ready.  Sends until
    ``seconds`` have passed, then completes the request in flight.
    ``keep(req, results)`` decides which requests stay for the check."""
    reqs: List[tr.Request] = []
    t0 = time.perf_counter()
    while not reqs or time.perf_counter() - t0 < seconds:
        req = tr.routed_request(traffic, len(reqs), layers, routings, seed)
        pairs = operands(req)
        req.sent_s = time.perf_counter() - t0
        with spans("submit"):
            rids = [server.submit(a, b) for a, b in pairs]
        with spans("flush"):
            out, failures = server.flush()
        lost = [rid for rid in rids if rid not in out]
        if lost:
            req.failure = failures.get(lost[0], "no result")
        else:
            ys = [out[rid] for rid in rids]
            with spans("wait"):
                _ready(ys)
            req.done_s = time.perf_counter() - t0
            keep(req, ys)
        reqs.append(req)
    return reqs


def open_loop(server, schedule: List[tr.Request], operands: Callable,
              spans: Spans) -> List[tr.Request]:
    """Requests are sent when due, whatever is still in flight.  Each
    flush serves everything that has come due; a waiter thread stamps
    each result when it is ready on the device."""
    done: "queue.Queue" = queue.Queue()
    t0 = time.perf_counter()

    def waiter():
        while True:
            item = done.get()
            if item is None:
                return
            req, y = item
            _ready(y)
            req.done_s = time.perf_counter() - t0
            req.result = y

    thread = threading.Thread(target=waiter, name="bench-waiter", daemon=True)
    thread.start()
    i, n = 0, len(schedule)
    try:
        while i < n:
            wait = schedule[i].due_s - (time.perf_counter() - t0)
            if wait > 0:
                with spans("sleep"):
                    time.sleep(wait)
                continue
            batch = {}
            while i < n and schedule[i].due_s <= time.perf_counter() - t0:
                req = schedule[i]
                a, b = operands(req)
                req.sent_s = time.perf_counter() - t0
                with spans("submit"):
                    batch[server.submit(a, b)] = req
                i += 1
            with spans("flush"):
                out, failures = server.flush()
            for rid, req in batch.items():
                if rid in out:
                    done.put((req, out[rid]))
                else:
                    req.failure = failures.get(rid, "no result")
    finally:
        done.put(None)
        with spans("wait"):
            thread.join()
    return schedule


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear between order statistics)."""
    return float(np.percentile(np.asarray(values, np.float64), q))

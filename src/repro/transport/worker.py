"""The out-of-process worker loop (DESIGN.md §13).

One worker owns one protocol slot ``n``.  It receives its plan
parameters over the wire, resolves the SAME data-independent tables the
dealer uses — :func:`repro.mpc.planner.get_plan` is deterministic
(invertibility-searched α's with fixed re-seeding), so a worker process
rebuilds bit-identical Vandermonde/G-mix tables without ever shipping
them — and then serves blocks until the socket closes:

* ``shares``  → run the plan's staged jit ``worker_compute`` program on
  its ``[1, …]`` share slice (phase 2 compute) and reply with its G-mix
  contribution ``g_n[n'] = c_{n,n'} · H(α_n) mod p`` for every receiver
  ``n'`` (phase-2 exchange, upstream half);
* ``ipoint``  → store this slot's aggregated ``I(α_n)`` and echo it back
  (phase-3 download) — the echo is what makes a late/dead worker a
  *phase-3* loss the survivor mask absorbs for free;
* ``chaos``   → test-only fault hooks (die/stall at a scripted block),
  driving the same schedules ``byzantine.FaultInjector`` serializes;
* ``stop``    → exit the loop.

Replies are cached per block id, so a dealer retry (duplicate request
after a deadline) is answered idempotently from the cache instead of
recomputing — exactly-once effects over at-least-once delivery.
"""
from __future__ import annotations

import socket
import time
from typing import Dict, Optional, Tuple

import numpy as np

from .framing import WIRE_VERSION, TransportClosed, recv_msg, send_msg

#: per-worker reply cache depth (blocks); must cover the dealer's largest
#: in-flight window plus retry skew
REPLY_CACHE = 8


def _build_state(doc: Dict):
    """Resolve (spec, plan, stages, slot) from a ``plan`` message."""
    from ..mpc.api import MPCSpec
    from ..mpc.field import Field

    if doc.get("wire") != WIRE_VERSION:
        raise TransportClosed(
            f"wire version {doc.get('wire')!r} != {WIRE_VERSION}")
    spec = MPCSpec(
        s=int(doc["s"]), t=int(doc["t"]), z=int(doc["z"]),
        lam=None if doc["lam"] is None else int(doc["lam"]),
        scheme=str(doc["scheme"]),
        field=Field(p=int(doc["p"]), frac_bits=int(doc["frac_bits"])),
        m=int(doc["m"]))
    plan = spec.plan()
    return spec, plan, plan.stages(), int(doc["device"])


class _Chaos:
    """Scripted fault hooks for one worker (test-only).

    ``die_block``/``die_after``: close the connection while serving that
    block — ``after="shares"`` is a phase-2 loss (no G contribution ever
    leaves), ``after="ipoint"`` a phase-3 loss (the I point exists but
    the download dies).  ``stall_block``/``stall_s``: sleep before
    replying, long enough to trip the dealer's deadline.
    """

    def __init__(self):
        self.die_block: Optional[int] = None
        self.die_after = "shares"
        self.stall_block: Optional[int] = None
        self.stall_s = 0.0

    def update(self, doc: Dict) -> None:
        if "die_block" in doc:
            self.die_block = (None if doc["die_block"] is None
                              else int(doc["die_block"]))
            self.die_after = str(doc.get("die_after", "shares"))
        if "stall_block" in doc:
            self.stall_block = (None if doc["stall_block"] is None
                                else int(doc["stall_block"]))
            self.stall_s = float(doc.get("stall_s", 0.0))

    def maybe_stall(self, bid: int) -> None:
        if self.stall_block is not None and bid == self.stall_block:
            time.sleep(self.stall_s)

    def dies_at(self, bid: int, point: str) -> bool:
        return self.die_block is not None and bid == self.die_block \
            and self.die_after == point


def worker_main(sock: socket.socket) -> None:
    """Serve one worker slot over ``sock`` until EOF/``stop``.

    Runs as a thread target (loopback tests: ``spawn="thread"``) or as
    the body of a spawned process (:func:`process_worker`).  All compute
    goes through the plan's staged jit programs — the same compiled
    stages the in-process backends dispatch.
    """
    plan = stages = None
    slot = -1
    g_row = None
    p = 0
    chaos = _Chaos()
    cache: Dict[Tuple[int, str], Tuple[Dict, Dict]] = {}
    try:
        while True:
            meta, arrays = recv_msg(sock, timeout=None)
            kind = meta.get("kind")
            if kind == "stop":
                return
            if kind == "chaos":
                chaos.update(meta)
                continue
            if kind == "plan":
                _, plan, stages, slot = _build_state(meta)
                p = plan.p
                # this slot's G-mix scalars c_{n, n'} for every receiver
                g_row = plan.g_mix[slot].astype(np.int64)
                cache.clear()
                send_msg(sock, {"kind": "ready", "device": slot,
                                "wire": WIRE_VERSION})
                continue
            bid = int(meta["block"])
            cached = cache.get((bid, kind))
            if cached is not None:  # dealer retry: answer idempotently
                cached[0]["mono"] = time.monotonic()
                send_msg(sock, *cached)
                continue
            chaos.maybe_stall(bid)
            if kind == "shares":
                t0 = time.perf_counter()
                h = stages.worker_compute(arrays["f_a"][None],
                                          arrays["f_b"][None])[0]
                # g_n[n', :] = c_{n,n'} · vec(H(α_n)) mod p — both factors
                # < p, so the product fits int64 exactly for any p < 2³¹·⁵
                # analysis: allow(host-sync): wire boundary, reply needs host bytes
                h_flat = np.asarray(h, np.int64).reshape(1, -1)
                g = (g_row[:, None] * h_flat) % p
                us = (time.perf_counter() - t0) * 1e6
                if chaos.dies_at(bid, "shares"):
                    return
                reply = ({"kind": "gvec", "block": bid, "device": slot,
                          "compute_us": us}, {"g": g})
            elif kind == "ipoint":
                if chaos.dies_at(bid, "ipoint"):
                    return
                reply = ({"kind": "result", "block": bid, "device": slot},
                         {"i": arrays["i"]})
            else:
                raise TransportClosed(f"unknown frame kind {kind!r}")
            cache[(bid, reply[0]["kind"])] = reply
            while len(cache) > REPLY_CACHE:
                cache.pop(next(iter(cache)))
            # send stamp for the dealer's simulated-latency delivery
            # (CLOCK_MONOTONIC is system-wide, so process mode works too)
            reply[0]["mono"] = time.monotonic()
            send_msg(sock, *reply)
    except (TransportClosed, OSError):
        return  # dealer hung up / killed the link: a clean worker death
    finally:
        try:
            sock.close()
        except OSError:
            pass


def process_worker(host: str, port: int, device: int) -> None:
    """Entry point for ``spawn="process"`` workers.

    The dealer starts it in a fresh interpreter whose environment pins
    JAX to the CPU; it connects back to the dealer's listener and
    identifies its slot with a ``hello`` frame before entering
    :func:`worker_main`.
    """
    sock = socket.create_connection((host, port), timeout=60.0)
    send_msg(sock, {"kind": "hello", "device": int(device),
                    "wire": WIRE_VERSION})
    sock.settimeout(None)
    worker_main(sock)

"""The one traffic generator: every mix is a data file it reads.

A mix file (``bench/traffic/<name>.json``) gives

* ``kind``: ``"closed"`` (one client sends its next product when the last
  one completes) or ``"open"`` (requests are due on a schedule, whether
  or not earlier ones have completed);
* ``rows``: rows of the activation operand (``T`` of a ``[T,K]×[K,F]``
  product: a prefill chunk, or 1 for a decode step);
* ``projections``: names of the configuration's projections the mix
  draws from (``bench/configs/<config>.json`` gives their ``[K, F]``);
* closed loops: ``operand_pool``, distinct activations per projection,
  used in turn, and ``check_sample``, how many products are compared;
* open loops: ``rate_rps``, which a cell may set in
  ``bench/cells/<workload>.json``.

A ``"routed"`` mix is a closed loop over one chip's share of a
routed-expert layer: one request is one projection applied to a prefill
chunk of ``tokens`` rows, as one product a held expert, each over the
rows the router sent that expert, served in one flush.  It reads
``tokens``, ``projections``, ``operand_pool`` and ``check_sample``; the
expert counts come from the configuration (:func:`chunk_routings`).  The
routing has no free parameter: each token picks ``top_k`` distinct
experts of all those routed over, every choice alike, the balance that
an MoE's load-balancing losses train toward.  Each chunk of the pool has
its own routing, so loads differ from request to request; the draws are
fixed by the mix and the configuration alone, so every seed routes the
same loads (the program compiles its eager tiling per row count, and the
warm-up builds each one), and the seed relabels which tokens they are.

A key the generator does not read is an error (:func:`validate`), so a
mix cannot ask for something it silently does not get.

Each product of a projection takes the weight of the next layer of the
configuration's ``num_hidden_layers`` in turn, so a window sweeps the
weights of the whole model, as serving its layers does.

The open loop's arrivals are Poisson, from the seeded generator of
``repro.sim.trace.ArrivalTrace.poisson`` with one change: the gaps are the
``n`` quantiles of the exponential law, put in an order drawn from the
seed, so every seed offers the same set of gaps and the same set of
projections, and only their order differs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

#: the keys each kind of mix is made of
KEYS = {
    "closed": {"kind", "rows", "projections", "operand_pool", "check_sample"},
    "open": {"kind", "rows", "projections", "rate_rps"},
    "routed": {"kind", "tokens", "projections", "operand_pool", "check_sample"},
}


@dataclasses.dataclass
class Request:
    """One product of the window and what became of it."""

    index: int
    projection: str
    operand: int          # row of that projection's activation pool
    layer: int = 0        # layer whose weight of that projection it takes
    due_s: float = 0.0    # seconds after the window opened
    sent_s: float = float("nan")
    done_s: float = float("nan")
    failure: str = ""
    result: object = None  # the device array, kept where it is compared

    @property
    def completed(self) -> bool:
        return not self.failure and np.isfinite(self.done_s)


@dataclasses.dataclass
class RoutedRequest(Request):
    """One projection of a routed layer over the held experts: ``tokens``
    gives, for each held expert in turn, the rows of the chunk routed to
    it; ``result`` is then the list of their products."""

    tokens: Tuple[np.ndarray, ...] = ()


def rng_for(seed: int) -> np.random.Generator:
    """A numpy generator from any whole-number seed (64 bits and more)."""
    return np.random.default_rng(abs(int(seed)))


def validate(traffic: dict) -> dict:
    """``traffic`` itself; raises on a kind or a key the generator does
    not read, and on one it needs that is missing."""
    kind = traffic.get("kind")
    if kind not in KEYS:
        raise ValueError(f"traffic kind {kind!r} is not one of {sorted(KEYS)}")
    extra, missing = set(traffic) - KEYS[kind], KEYS[kind] - set(traffic)
    if extra or missing:
        raise ValueError(f"a {kind} mix has the keys {sorted(KEYS[kind])}; "
                         f"unknown: {sorted(extra)}, missing: {sorted(missing)}")
    return traffic


def closed_request(traffic: dict, index: int, layers: int) -> Request:
    """The ``index``-th product of a closed loop: projections alternate,
    activations cycle through the pool and weights through the layers."""
    names = traffic["projections"]
    turn = index // len(names)
    return Request(index=index, projection=names[index % len(names)],
                   operand=turn % traffic["operand_pool"], layer=turn % layers)


def open_schedule(traffic: dict, seed: int, seconds: float, layers: int
                  ) -> List[Request]:
    """The due times, projections and layers of an open-loop window."""
    rate = float(traffic["rate_rps"])
    if rate <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate}")
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed)
    quantiles = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-quantiles) / rate)
    due = np.cumsum(gaps) - gaps[0]          # first arrival at t = 0
    names = traffic["projections"]
    kinds = rng.permutation(np.resize(np.arange(len(names)), n))
    seen: Dict[int, int] = {}
    out = []
    for i, (d, k) in enumerate(zip(due, kinds, strict=True)):
        turn = seen.get(k, 0)
        out.append(Request(index=i, projection=names[k], operand=turn,
                           layer=turn % layers, due_s=float(d)))
        seen[k] = seen.get(k, 0) + 1
    return out


def balanced_routing(tokens: int, experts: int, top_k: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``[tokens, top_k]``: each token's ``top_k`` distinct experts of
    ``experts``, every choice alike."""
    return np.argsort(rng.random((tokens, experts)), axis=1)[:, :top_k]


def held_experts(experts: int, held: int, offset: int = 0) -> np.ndarray:
    """The experts that the chip at ``offset`` holds when ``experts`` lie
    over ``experts // held`` chips: ``offset`` and every ``chips``-th
    expert after it."""
    chips = experts // held
    if chips * held != experts or not 0 <= offset < chips:
        raise ValueError(f"{experts} experts do not lie {held} a chip, "
                         f"or offset {offset} names no chip")
    return offset + chips * np.arange(held)


def chunk_routings(traffic: dict, config: dict
                   ) -> List[Tuple[np.ndarray, ...]]:
    """For each chunk of the pool, the rows (token indices, ascending)
    routed to each expert the chip holds: of the configuration's
    ``published.n_routed_experts`` experts, each token routed to
    ``model.num_experts_per_tok`` (:func:`balanced_routing`), the chip
    holds ``model.n_routed_experts``.  Chunk ``j``'s draw depends on the
    mix's and the configuration's sizes and ``j`` alone."""
    model = config["model"]
    tokens, top_k = traffic["tokens"], model["num_experts_per_tok"]
    experts = config["published"]["n_routed_experts"]
    held = held_experts(experts, model["n_routed_experts"])
    out = []
    for j in range(traffic["operand_pool"]):
        picks = balanced_routing(tokens, experts, top_k, np.random.default_rng(
            [tokens, experts, top_k, j]))
        out.append(tuple(np.flatnonzero((picks == e).any(axis=1)).astype(np.int32)
                         for e in held))
    return out


def routed_request(traffic: dict, index: int, layers: int, routings,
                   seed: int) -> RoutedRequest:
    """The ``index``-th request of a routed loop: projection, chunk and
    layer as :func:`closed_request` has them, the chunk's routing with its
    tokens relabelled by a permutation drawn from the seed and the chunk."""
    base = closed_request(traffic, index, layers)
    perm = np.random.default_rng([abs(int(seed)), base.operand]).permutation(
        traffic["tokens"]).astype(np.int32)
    return RoutedRequest(index=index, projection=base.projection,
                         operand=base.operand, layer=base.layer,
                         tokens=tuple(np.sort(perm[rows])
                                      for rows in routings[base.operand]))


def pool_sizes(traffic: dict, schedule: List[Request]) -> Dict[str, int]:
    """Activations each projection needs: the closed (or routed) loop's
    pool, or one per open-loop request of that projection."""
    if traffic["kind"] in ("closed", "routed"):
        return {p: traffic["operand_pool"] for p in traffic["projections"]}
    sizes = {p: 0 for p in traffic["projections"]}
    for r in schedule:
        sizes[r.projection] = max(sizes[r.projection], r.operand + 1)
    return sizes

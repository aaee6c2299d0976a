"""One run of one cell: set-up, warm-up, the window, the check, metrics.

:func:`run_cell` is what ``bench/run.py`` calls for
``--workload --seed --seconds --trace``; ``bench/control.py`` and the
tests call it with another server in the program's place, or on the CPU
at a small size.
"""
from __future__ import annotations

import dataclasses
import functools
import re
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import cells, reference, trace as tr_mod, traffic as tr, window, work

#: JAX lowers a program to a module exactly when it needs an executable it
#: does not hold in memory: a compilation, or a load from the disk cache
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileCounter:
    """Counts the executables JAX builds while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.active and event == LOWERING_EVENT:
            self.count += 1


def use_compile_cache(root) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``; every program is kept."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        str(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_key(seed: int):
    """A JAX key from any whole-number seed, all of its bits used."""
    import jax

    s = abs(int(seed))
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    s >>= 32
    while s:
        key = jax.random.fold_in(key, s & 0xFFFFFFFF)
        s >>= 32
    return key


def chips_for(cell: cells.Cell, require_tpu: bool):
    """The devices the cell runs on; raises :class:`NoDevice`."""
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        raise NoDevice(f"the cell asks for {cell.chips} chips, JAX found "
                       f"{len(devices)}")
    return devices[:cell.chips]


def make_operands(cell: cells.Cell, pools: Dict[str, int], key):
    """Every operand of the run, made on the device in one jitted call:
    ``{projection: ([activation per request], [weight per layer])}``,
    float32."""
    import jax
    import jax.numpy as jnp

    cfg, rows = cell.config, cell.traffic["rows"]
    layers = cfg["model"]["num_hidden_layers"]
    shapes = tuple((p, pools[p], *cfg["projections"][p])
                   for p in cell.traffic["projections"])
    act_std = cfg["operands"]["activation_std"]
    w_std = cfg["operands"]["weight_std"]

    def make(key):
        out = {}
        for i, (name, count, k, f) in enumerate(shapes):
            ka, kw = jax.random.split(jax.random.fold_in(key, i))
            acts = act_std * jax.random.normal(ka, (count, rows, k), jnp.float32)
            out[name] = ([acts[j] for j in range(count)],
                         [w_std * jax.random.normal(jax.random.fold_in(kw, j),
                                                    (k, f), jnp.float32)
                          for j in range(layers)])
        return out

    return jax.block_until_ready(jax.jit(make)(key))


def make_routed_operands(cell: cells.Cell, pools: Dict[str, int], key):
    """Every operand of a routed run, made on the device in one jitted
    call: ``{projection: ([activation chunk per request], weights)}``,
    float32, ``weights[layer, expert]`` the held expert's ``[K, F]``."""
    import jax
    import jax.numpy as jnp

    cfg, rows = cell.config, cell.traffic["tokens"]
    layers = cfg["model"]["num_hidden_layers"]
    experts = cfg["model"]["n_routed_experts"]
    shapes = tuple((p, pools[p], *cfg["projections"][p])
                   for p in cell.traffic["projections"])
    act_std = cfg["operands"]["activation_std"]
    w_std = cfg["operands"]["weight_std"]

    def make(key):
        out = {}
        for i, (name, count, k, f) in enumerate(shapes):
            ka, kw = jax.random.split(jax.random.fold_in(key, i))
            acts = act_std * jax.random.normal(ka, (count, rows, k), jnp.float32)
            keys = jax.vmap(jax.random.fold_in, (None, 0))(
                kw, jnp.arange(layers * experts))
            w = jax.vmap(functools.partial(jax.random.normal, shape=(k, f),
                                           dtype=jnp.float32))(keys)
            out[name] = ([acts[j] for j in range(count)],
                         w_std * w.reshape(layers, experts, k, f))
        return out

    return jax.block_until_ready(jax.jit(make)(key))


class Reservoir:
    """A uniform sample, drawn from the seed, of ``size`` requests whose
    results are kept for the check (the others are let go)."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = tr.rng_for(seed)
        self.kept: List[tr.Request] = []
        self.seen = 0

    def __call__(self, req: tr.Request, result) -> None:
        self.seen += 1
        if len(self.kept) < self.size:
            req.result = result
            self.kept.append(req)
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.size:
            self.kept[j].result = None
            req.result = result
            self.kept[j] = req


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader reads."""

    cell: cells.Cell
    requests: List[tr.Request]
    trace: Optional[tr_mod.Trace]
    window_ns: Optional[tuple]
    devices: List[int]
    counters: Dict[str, int]
    spans: Dict[str, List[float]]
    blocks: Dict[tuple, int]
    peaks: Dict[str, float]

    @property
    def completed(self) -> int:
        return sum(r.completed for r in self.requests)

    @property
    def chips(self) -> int:
        return self.cell.chips

    def least_time_per_product(self):
        """``(seconds, bound)``: the least time of the window's blocks on
        its chips, per completed product."""
        total, kinds = 0.0, set()
        for (n, s, t, z, m), count in self.blocks.items():
            sec, kind = work.block_least_time(n, s, t, z, m, self.peaks,
                                              self.chips)
            total += count * sec
            kinds.add(kind)
        return total / max(1, self.completed), "+".join(sorted(kinds))


def _check(cell: cells.Cell, reqs: List[tr.Request], operands) -> Dict:
    """Compare every kept result with the exact fixed-point reference."""
    spec = cell.config["spec"]
    f, p = spec["frac_bits"], spec["p"]
    kept = [r for r in reqs if r.completed and r.result is not None]
    compare = _routed_gap if cell.traffic["kind"] == "routed" else _dense_gap
    gap, compared = compare(cell, kept, operands, f, p)
    limits = cell.config["limits"]
    missing = sum(not r.completed for r in reqs)
    return {"compared": compared,
            "checks": {"fixed_point_gap": {"value": gap,
                                           "limit": limits["fixed_point_gap"]},
                       "missing": {"value": missing,
                                   "limit": limits["missing"]}}}


def _dense_gap(cell, kept, operands, f: int, p: int):
    """``(largest gap, products compared)`` of one product a request."""
    gap, compared = 0.0, 0
    by_weight: Dict[tuple, List[tr.Request]] = {}
    for r in kept:
        by_weight.setdefault((r.projection, r.layer), []).append(r)
    for (proj, layer), group in by_weight.items():
        acts, weights = operands[proj]
        w = np.asarray(weights[layer])
        a = np.concatenate([np.asarray(acts[r.operand]) for r in group])
        ref = reference.exact_product(a, w, f, p)
        rows = cell.traffic["rows"]
        for i, r in enumerate(group):
            y = np.asarray(r.result)
            gap = max(gap, reference.fixed_point_gap(
                y, ref[i * rows:(i + 1) * rows], f))
            compared += 1
    return gap, compared


def _routed_gap(cell, kept, operands, f: int, p: int):
    """``(largest gap, products compared)`` over every held expert's
    product of each kept routed request: the chunk's routed rows times
    that expert's weight."""
    gap, compared = 0.0, 0
    for r in kept:
        acts, weights = operands[r.projection]
        chunk = np.asarray(acts[r.operand])
        for e, (rows, y) in enumerate(zip(r.tokens, r.result, strict=True)):
            w = np.asarray(weights[r.layer, e])
            ref = reference.exact_product(chunk[rows], w, f, p)
            gap = max(gap, reference.fixed_point_gap(np.asarray(y), ref, f))
            compared += 1
    return gap, compared


def end_to_end(name: str, reqs: List[tr.Request], setup_s: float) -> float:
    """The end-to-end metric ``name``; a suffix after a dot names the
    cells that report it apart (``products_per_s.sharded4``) and does not
    change how it is measured."""
    name = name.split(".", 1)[0]
    if name == "setup_s":
        return setup_s
    done = [r for r in reqs if r.completed]
    if name == "products_per_s":
        span = max(r.done_s for r in done) - min(r.sent_s for r in done)
        return len(done) / span
    m = re.fullmatch(r"latency_p(\d+)_ms", name)
    if m:
        return window.percentile([1e3 * (r.done_s - r.due_s) for r in done],
                                 float(m.group(1)))
    raise ValueError(f"no end-to-end metric {name!r} in the harness")


def routed_warmup(cell: cells.Cell, routings, pools: Dict[str, int]
                  ) -> List[tr.RoutedRequest]:
    """Warm-up requests that build every shape a routed window uses: the
    program builds its tiling per row count and projection shape, so one
    request a chunk's routing and shape, over the spare activation, with
    only the experts whose row count is new for that shape."""
    out, seen = [], set()
    for p in cell.traffic["projections"]:
        shape = tuple(cell.config["projections"][p])
        for chunk in routings:
            fresh = []
            for rows in chunk:
                if (shape, rows.size) not in seen:
                    seen.add((shape, rows.size))
                    fresh.append(rows)
            if fresh:
                out.append(tr.RoutedRequest(index=-1, projection=p,
                                            operand=pools[p],
                                            tokens=tuple(fresh)))
    return out


def run_cell(cell: cells.Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, compiles: CompileCounter,
             server_factory: Optional[Callable] = None,
             require_tpu: bool = True,
             on_requests: Optional[Callable] = None) -> Dict:
    """One run; returns the result line's object (``checks`` last).

    ``server_factory`` puts another server in the program's place (the
    control); ``on_requests`` is handed the window's requests."""
    import jax

    from .server import ProgramServer

    devices = chips_for(cell, require_tpu)
    kind = devices[0].device_kind
    peaks = cells.device_peaks(kind) if require_tpu else {}
    traffic = cell.traffic
    closed = traffic["kind"] == "closed"
    routed = traffic["kind"] == "routed"
    layers = cell.config["model"]["num_hidden_layers"]
    schedule = ([] if closed or routed
                else tr.open_schedule(traffic, seed, seconds, layers))
    pools = tr.pool_sizes(traffic, schedule)     # one more row each: warm-up
    key = device_key(seed)
    make = make_routed_operands if routed else make_operands
    operands = make(cell, {p: n + 1 for p, n in pools.items()},
                    jax.random.fold_in(key, 0))
    server = (server_factory or (lambda: ProgramServer(
        cell.config, devices, jax.random.fold_in(key, 1))))()

    def pair(req):
        acts, weights = operands[req.projection]
        return acts[req.operand], weights[req.layer]

    # warm-up: each projection alone, then all of them in one flush
    warm = [tr.Request(index=-1, projection=p, operand=pools[p])
            for p in traffic["projections"]]
    batches = [[w] for w in warm] + ([warm] if not closed else [])
    if routed:
        routings = tr.chunk_routings(traffic, cell.config)
        take = jax.jit(lambda chunk, rows, w, layer, e: (chunk[rows],
                                                         w[layer, e]))

        def experts(req):
            """One operand pair a held expert, taken on the device: its
            routed rows of the chunk and its weight."""
            acts, weights = operands[req.projection]
            return [take(acts[req.operand], rows, weights, req.layer, e)
                    for e, rows in enumerate(req.tokens)]

        batches = [[r] for r in routed_warmup(cell, routings, pools)]

    def submit(batch):
        """The warm-up batch's request ids, its operands made here."""
        if routed:
            return [server.submit(a, b) for a, b in experts(batch[0])]
        return [server.submit(*pair(w)) for w in batch]

    for batch in batches:
        rids = submit(batch)
        out, failures = server.flush()
        for rid in rids:
            if rid not in out:
                raise RuntimeError(f"warm-up failed: {failures.get(rid)}")
        jax.block_until_ready(list(out.values()))
    before = server.counters()
    blocks_before = dict(server.blocks)
    spans = window.Spans(traced)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(log_dir)
    setup_s = time.perf_counter() - t_start
    compiles.active = True
    try:
        with spans("window"):
            if routed:
                keep = Reservoir(traffic["check_sample"], seed)
                reqs = window.routed_loop(server, traffic, layers, routings,
                                          seed, experts, seconds, spans, keep)
            elif closed:
                keep = Reservoir(traffic["check_sample"], seed)
                reqs = window.closed_loop(server, traffic, layers, pair,
                                          seconds, spans, keep)
            else:
                reqs = window.open_loop(server, schedule, pair, spans)
    finally:
        compiles.active = False
        if traced:
            jax.profiler.stop_trace()
    window_compiles = compiles.count
    compiles.count = 0
    after = server.counters()
    counters = {k: after[k] - before.get(k, 0) for k in after}
    blocks = {k: v - blocks_before.get(k, 0) for k, v in server.blocks.items()}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    trace = None
    if traced:
        trace = tr_mod.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
    check = _check(cell, reqs, operands)
    if on_requests is not None:
        on_requests(reqs)
    del server, operands

    view = RunView(cell=cell, requests=reqs, trace=trace,
                   window_ns=trace.window() if trace else None,
                   devices=[d.id for d in devices], counters=counters,
                   spans=spans.seconds, blocks=blocks, peaks=peaks)
    metrics = {}
    if traced:
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": end_to_end(m["name"], reqs, setup_s),
                                  "unit": m["unit"]}
    late = [r.sent_s - r.due_s for r in reqs if not (closed or routed)]
    result = {
        "correct": (check["compared"] > 0 and all(
            c["value"] <= c["limit"] for c in check["checks"].values())),
        "attempted": len(reqs),
        "failed": sum(not r.completed for r in reqs),
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(jax.devices()), "memory_peak_bytes": int(peak)},
        "window": {"compiles": window_compiles, "compared": check["compared"],
                   "lateness_p90_ms": (1e3 * window.percentile(late, 90)
                                       if late else 0.0)},
    }
    if trace is not None:
        lo, hi = view.window_ns
        result["device"]["busy_s"] = tr_mod.busy_ns(trace, view.devices, lo, hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": tr_mod.top_ops(trace, view.devices, lo, hi),
            "idle_gaps": tr_mod.idle_by_host(trace, view.devices[0], lo, hi)}
    result["checks"] = check["checks"]
    return result

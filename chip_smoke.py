#!/usr/bin/env python3
"""Drive the coded-MPC main path once on a TPU and check every result.

    python chip_smoke.py [--seed N]          # one chip: phases (a)-(e)
    python chip_smoke.py --four-chips        # four chips: the sharded path

Everything goes through the user entry point ``repro.mpc.connect`` at the
sizes the engine serves, with inputs drawn from ``--seed``:

(a) exact field product ``[2048,2048]x[2048,2048]`` (encoded residues),
    local session, both primes, against an exact int64 host reference on
    sampled output rows;
(b) the same product decoded from a random quorum of ``t²+z`` survivors
    (11 of 17 workers dropped): bit-identical to (a);
(c) private-inference projection at published width: llama3.2-1b
    ``lm_head`` ``[1,2048]x[2048,128256]`` in floats (63 coded blocks);
(d) ``backend="batched"``: 8 submits of ``[1,2048]x[2048,8192]`` (the
    llama3.2-1b MLP up-projection) and one flush;
(e) ``backend="remote"`` (thread-mode workers): ``[512,512]`` encoded,
    bit-identical to the local session.

The float phases (c)-(d) are checked twice: bit-exactly against the
fixed-point integer product ``round(h·2^f) @ round(W·2^f) / 2^{2f}``
computed on the host, and against float32 ``highest`` on the chip within
the rounding bound of ``f = frac_bits`` fractional bits over K terms.

With ``--four-chips`` the script runs only ``connect(spec,
backend="sharded", mesh=<4 chips>)`` on the inputs of (a) and compares it
bit for bit with the one-chip local session.

Each phase prints one JSON line (shapes, compile and run seconds, check);
the last line is ``{"ok": true, "device": {...}}``.  The script exits
non-zero, without that line, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
M = 2048          # phase (a) side; llama3.2-1b hidden width
VOCAB = 128256    # llama3.2-1b vocabulary (lm_head columns)
FFN = 8192        # llama3.2-1b MLP width
SAMPLED_ROWS = 64


def exact_rows(a, b, p, rows):
    """``(a[rows] @ b) mod p`` in int64 numpy, exactly.

    ``b = hi·2¹⁶ + lo`` with ``hi, lo < 2¹⁶``: each partial sum is below
    ``K·2³¹·2¹⁶ < 2⁶³`` for ``K < 2¹⁶``, and the recombination below
    ``2⁴⁷ + 2⁵⁸``.  Independent of the code under test.
    """
    a = np.asarray(a, np.int64)[rows]
    b = np.asarray(b, np.int64)
    if a.shape[1] >= 1 << 16:
        raise ValueError("exact_rows needs K < 2^16")
    hi, lo = b >> 16, b & 0xFFFF
    return ((a @ hi % p) * 65536 + a @ lo) % p


def timed(fn):
    """``(result as numpy, seconds)`` for one call, device work included."""
    t0 = time.perf_counter()
    out = np.asarray(fn())
    return out, time.perf_counter() - t0


def cold_warm(fn):
    """Call twice: ``(result, compile_s, run_s)`` — the first call's extra
    time over the second is compilation."""
    first, t_cold = timed(fn)
    out, t_warm = timed(fn)
    if not np.array_equal(first, out):
        raise AssertionError("two calls with one key disagree")
    return out, t_cold - t_warm, t_warm


def report(phase, **kw):
    print(json.dumps({"phase": phase, **kw}), flush=True)


def require(ok, what):
    if not ok:
        raise AssertionError(what)


def fixed_point_checks(y, h, w, ref32, frac_bits):
    """Exact fixed-point identity and the float32 rounding bound.

    * exact: ``y·2^{2f}`` equals ``round(h·2^f) @ round(W·2^f)`` (int64,
      exact: the quantized factors are below 2¹⁶ in magnitude here);
    * bound: rounding each factor to ``f`` fractional bits moves each
      product by at most ``2^{-(f+1)}(|h_k| + |W_kj|) + 2^{-2(f+1)}``, so
      ``|y − y32| ≤ 2^{-(f+1)}(Σ|h_k| + Σ_k|W_kj|) + K·2^{-2(f+1)}`` plus
      float32's own accumulation error ``K·2⁻²⁴·Σ_k|h_k W_kj|``.
    Returns the largest ``|y − y32|`` and its largest allowed value.
    """
    scale = float(1 << frac_bits)
    hq = np.rint(h.astype(np.float64) * scale).astype(np.int64)
    wq = np.rint(w.astype(np.float64) * scale).astype(np.int64)
    want = hq @ wq
    got = np.rint(y.astype(np.float64) * scale * scale).astype(np.int64)
    require(np.array_equal(got, want),
            f"fixed-point product differs in {int((got != want).sum())} "
            "entries")
    k = h.shape[-1]
    ah, aw = np.abs(h.astype(np.float64)), np.abs(w.astype(np.float64))
    half = 2.0 ** -(frac_bits + 1)
    tol = (half * (ah.sum(-1, keepdims=True) + aw.sum(0)) + k * half * half
           + k * 2.0 ** -24 * (ah @ aw))
    err = np.abs(y.astype(np.float64) - ref32.astype(np.float64))
    require(bool((err <= tol).all()), "float32 rounding bound exceeded")
    return float(err.max()), float(tol.min())


def phase_field(connect, MPCSpec, Field, p, rng, key, *, survivors=None):
    """Phases (a) and (b) for one prime; returns the result for reuse."""
    spec = MPCSpec(s=2, t=2, z=2, field=Field(p))
    sess = connect(spec)
    a = rng.integers(0, p, (M, M))
    b = rng.integers(0, p, (M, M))
    y, c_s, r_s = cold_warm(lambda: sess.matmul(a, b, encoded=True, key=key))
    rows = np.sort(rng.choice(M, SAMPLED_ROWS, replace=False))
    require(np.array_equal(y[rows], exact_rows(a, b, p, rows)),
            f"(a) p={p}: field product differs from the exact reference")
    report("a", p=p, shapes=[[M, M], [M, M]], workers=spec.n_workers,
           compile_s=c_s, run_s=r_s,
           check=f"exact on {SAMPLED_ROWS} sampled rows", ok=True)
    n = spec.n_workers
    quorum = spec.t * spec.t + spec.z
    alive = np.zeros(n, bool)
    alive[rng.choice(n, quorum, replace=False)] = True
    yb, c_s, r_s = cold_warm(lambda: sess.matmul(
        a, b, encoded=True, key=key, survivors=alive))
    require(np.array_equal(yb, y), f"(b) p={p}: survivor decode != (a)")
    report("b", p=p, shapes=[[M, M], [M, M]],
           alive=[int(i) for i in np.nonzero(alive)[0]],
           dropped=int(n - quorum), compile_s=c_s, run_s=r_s,
           check="bit-identical to (a)", ok=True)
    return a, b, y


def one_chip(args, jax, jnp):
    from repro.mpc import MPCSpec, connect
    from repro.mpc.field import Field, P_DEFAULT, P_MERSENNE31

    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    for p in (P_DEFAULT, P_MERSENNE31):
        phase_field(connect, MPCSpec, Field, p, rng, key)

    # (c) private lm_head projection: hidden ~ N(0, 1) (post-norm scale),
    # weights ~ N(0, 0.02²) (the model's init scale), both from the seed
    spec = MPCSpec(s=2, t=2, z=2)
    f = spec.field
    kh, kw, kd = jax.random.split(jax.random.fold_in(key, 1), 3)
    h = jax.random.normal(kh, (1, M), jnp.float32)
    w = 0.02 * jax.random.normal(kw, (M, VOCAB), jnp.float32)
    sess = connect(spec)
    y, c_s, r_s = cold_warm(lambda: sess.matmul(h, w, key=key))
    ref32 = np.asarray(jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST))
    err, tol = fixed_point_checks(y, np.asarray(h), np.asarray(w), ref32,
                                  f.frac_bits)
    report("c", shapes=[[1, M], [M, VOCAB]], blocks=sess.stats["blocks"] // 2,
           compile_s=c_s, run_s=r_s, max_abs_err=err, min_tol=tol,
           check="exact fixed-point product; float32 highest within the "
                 f"frac_bits={f.frac_bits}, K={M} rounding bound", ok=True)

    # (d) batched engine: 8 users' decode-step MLP up-projections, one flush
    hs = jax.random.normal(kd, (8, 1, M), jnp.float32)
    w_up = 0.02 * jax.random.normal(jax.random.fold_in(kd, 1), (M, FFN),
                                    jnp.float32)
    bsess = connect(spec, backend="batched")

    def flush():
        rids = [bsess.submit(hs[i], w_up, key=jax.random.fold_in(key, i))
                for i in range(8)]
        out = bsess.flush()
        require(not bsess.failures, f"(d) failures: {bsess.failures}")
        return np.stack([np.asarray(out[r]) for r in rids])

    ys, c_s, r_s = cold_warm(flush)
    ref_up = np.asarray(jnp.einsum("bik,kn->bin", hs, w_up,
                                   precision=jax.lax.Precision.HIGHEST))
    worst = 0.0
    for i in range(8):
        err, _ = fixed_point_checks(ys[i], np.asarray(hs[i]),
                                    np.asarray(w_up), ref_up[i], f.frac_bits)
        worst = max(worst, err)
    report("d", shapes=[[1, M], [M, FFN]], requests=8, compile_s=c_s,
           run_s=r_s, max_abs_err=worst,
           check="each request: exact fixed-point product; float32 highest "
                 "within the rounding bound", ok=True)

    # (e) out-of-process worker protocol, thread-mode workers
    side = 512
    a = rng.integers(0, f.p, (side, side))
    b = rng.integers(0, f.p, (side, side))
    want = np.asarray(connect(spec).matmul(a, b, encoded=True, key=key))
    rem = connect(spec, backend="remote")
    try:
        y, c_s, r_s = cold_warm(
            lambda: rem.matmul(a, b, encoded=True, key=key))
    finally:
        rem.backend.close()
    require(np.array_equal(y, want), "(e) remote != local")
    report("e", shapes=[[side, side], [side, side]], spawn="thread",
           compile_s=c_s, run_s=r_s, check="bit-identical to local", ok=True)


def four_chips(args, jax):
    from jax.sharding import Mesh

    from repro.mpc import MPCSpec, connect
    from repro.mpc.field import Field, P_DEFAULT, P_MERSENNE31

    devices = jax.devices()
    require(len(devices) >= 4, f"--four-chips needs 4 devices, got "
                               f"{len(devices)}")
    mesh = Mesh(np.asarray(devices[:4]), ("model",))
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    for p in (P_DEFAULT, P_MERSENNE31):
        spec = MPCSpec(s=2, t=2, z=2, field=Field(p))
        a = rng.integers(0, p, (M, M))
        b = rng.integers(0, p, (M, M))
        want = np.asarray(connect(spec).matmul(a, b, encoded=True, key=key))
        sess = connect(spec, backend="sharded", mesh=mesh)
        y, c_s, r_s = cold_warm(
            lambda: sess.matmul(a, b, encoded=True, key=key))
        require(np.array_equal(y, want), f"p={p}: sharded != one-chip local")
        runner = sess.backend.runner(spec.protocol(M))
        i_pts = runner.shares(a.T, b, key)
        placed = {s.device.id for s in i_pts.addressable_shards}
        require(len(placed) == 4, f"shards sit on devices {sorted(placed)}")
        report("sharded", p=p, shapes=[[M, M], [M, M]],
               mesh=[int(d.id) for d in mesh.devices.flat],
               shard_devices=sorted(placed), compile_s=c_s, run_s=r_s,
               host_bytes=sess.stats["host_bytes"],
               mesh_bytes=sess.stats["mesh_bytes"],
               check="bit-identical to the one-chip local result", ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on four chips")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.runtime import use_compile_cache

    use_compile_cache(HERE)
    if args.four_chips:
        four_chips(args, jax)
    else:
        one_chip(args, jax, jnp)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

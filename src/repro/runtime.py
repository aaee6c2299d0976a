"""Where the program runs: the one platform check, the compile cache and
the program's host spans.

* :func:`on_tpu` is the single place that asks which backend JAX drives.
  Pallas interpret mode (:func:`pallas_interpret`) and the ``mode="pallas"``
  refusal (:meth:`repro.mpc.protocol.AGECMPCProtocol.run`) both read it,
  so a test steers every platform branch by monkeypatching this one
  function.
* :func:`use_compile_cache` points JAX's persistent compilation cache at
  ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself) and
  at ``<checkout>/.jax_cache`` otherwise — a fixed path, since the path is
  part of the cache key.
* :func:`span` is the one way the program marks a host span: a
  ``jax.profiler.TraceAnnotation`` named ``mpc.<name>``.  It is recorded
  only while a profiler session runs (``jax.profiler.start_trace``), on
  the device trace's clock, and otherwise costs one constructor call.
  Device work is marked with ``jax.named_scope`` inside the jitted
  programs instead: metadata of the compiled ops, free at run time.
"""
from __future__ import annotations

import os

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Pallas kernels compile for the TPU and run interpreted elsewhere."""
    return not on_tpu()


def use_compile_cache(checkout: str) -> str:
    """Turn on the persistent compile cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def span(name: str, **ids: int) -> jax.profiler.TraceAnnotation:
    """A host span ``mpc.<name>`` carrying the ids of its request
    (``rid``, ``block``) as trace metadata."""
    return jax.profiler.TraceAnnotation("mpc." + name, **ids)

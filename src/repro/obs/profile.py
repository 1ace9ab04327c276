"""Opt-in ``jax.profiler`` capture, named phase scopes and host spans.

Hooks, all zero-cost when unused:

* ``trace_capture(outdir)`` — a context manager around
  ``jax.profiler.start_trace``/``stop_trace``. The captured trace lands
  under ``outdir`` as a Perfetto/TensorBoard artifact directory
  (``tensorboard --logdir outdir`` or ui.perfetto.dev). Pass
  ``enabled=False`` to turn the whole block into a no-op — callers can
  thread a ``--trace`` flag without branching.
* ``span(name)`` — a host-side ``jax.profiler.TraceAnnotation``: marks a
  named region on the profiler timeline, on the clock the device events
  share. The serving engine marks each layer of a call with one
  (``serve/price``, ``serve/decode``). Inside jit-traced code use
  ``phase(name)`` instead — a ``jax.named_scope`` that names the
  emitted HLO, so compiled-program profiles attribute device time to
  actor/critic/env/train phases.
* ``pull(x, counts)`` — one blocking device->host read under a
  ``serve/pull`` span, counted in ``counts["host_pulls"]`` so the same
  number is readable without a trace.

With no profiler session a span only checks whether one is active.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import numpy as np


def phase(name: str):
    """Named scope for *traced* code: names the HLO ops under it.

    Use inside jit/vmap/scan bodies; compiles to metadata only (no
    runtime cost, no numerics change).
    """
    return jax.named_scope(f"obs/{name}")


def span(name: str):
    """Profiler annotation for *host-side* code (serving loop, bench
    harnesses). Shows up as a named region in captured traces; ~free
    when no trace is active."""
    return jax.profiler.TraceAnnotation(name)


def pull(x, counts: Optional[dict] = None) -> np.ndarray:
    """Read ``x`` to the host under a ``serve/pull`` span; the span holds
    the conversion and nothing else, so its length is the wait for the
    device. Adds one to ``counts["host_pulls"]`` when ``counts`` is
    given."""
    with span("serve/pull"):
        out = np.asarray(x)
    if counts is not None:
        counts["host_pulls"] += 1
    return out


@contextlib.contextmanager
def trace_capture(outdir: str, *, enabled: bool = True):
    """Capture a jax profiler trace into ``outdir`` while the block runs.

    ``enabled=False`` makes this a no-op so call sites can thread an
    opt-in flag straight through. The directory is created. A capture
    that fails to start (e.g. another trace already active) raises: a
    run that asked for a trace must not silently come back without one.
    """
    if not enabled:
        yield None
        return
    os.makedirs(outdir, exist_ok=True)
    jax.profiler.start_trace(outdir)
    try:
        yield outdir
    finally:
        jax.profiler.stop_trace()

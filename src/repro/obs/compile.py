"""Compile-count tracking: ``jax.monitoring`` events + per-function pins.

The repo's scaling story rests on compile-count invariants ("a 4-method
x seeds x scenarios grid is 2 compiled programs"), but until now the
counting was ad-hoc — each benchmark ``--guard`` poked the jax-internal
``_cache_size`` by hand. ``CompileTracker`` packages both measurement
levels behind one context manager:

* **Event stream** — while the context is active, every
  ``/jax/core/compile/*`` duration event (jaxpr trace, MLIR lowering,
  backend compile) is recorded. This sees *all* compilation in the
  process, including eager-op fallbacks and jit caches warmed by other
  code, so it is a logging/telemetry signal (how much wall-clock went
  to XLA?), not an exact per-program assertion.
* **Tracked functions** — ``track(name, fn)`` registers a jitted
  callable; ``counts()`` reads each one's compile-cache size. A freshly
  constructed jit wrapper starts at zero entries, so this is the exact
  per-program count the pack guards assert — unaffected by anything
  else the process compiled.

``use_compile_cache()`` places JAX's persistent compilation cache; every
entry point calls it once before it compiles anything.

Usage::

    with CompileTracker() as ct:
        prog = PackProgram(pack)
        prog.run(); prog.run()
        ct.track(pack.label(), prog._episode)
    ct.assert_counts({pack.label(): 1})
    log(ct.summary())   # n_compiles, total_compile_s, per-event durations
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The duration event XLA emits once per actual backend compilation.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENT_PREFIX = "/jax/core/compile/"


# <checkout>/.jax_cache (this file is <checkout>/src/repro/obs/compile.py):
# fixed, because a later process must find the same directory again.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here. Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``, so one run's compiles are found again by
    the next run from the same checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


class CompileTracker:
    """Context manager that counts XLA compilations while active."""

    def __init__(self):
        self.events: list = []       # (event name, duration seconds)
        self._tracked: dict = {}     # name -> jitted callable
        self._active = False

    # ------------------------------------------------------------- context
    def __enter__(self) -> "CompileTracker":
        def listener(name, duration, **kw):
            if self._active and name.startswith(COMPILE_EVENT_PREFIX):
                self.events.append((name, float(duration)))

        self._listener = listener
        self._active = True
        jax.monitoring.register_event_duration_secs_listener(listener)
        return self

    def __exit__(self, *exc) -> None:
        self._active = False
        jax.monitoring.unregister_event_duration_listener(self._listener)

    # ------------------------------------------------------- event stream
    @property
    def n_backend_compiles(self) -> int:
        """Process-wide backend compilations observed while active."""
        return sum(1 for n, _ in self.events if n == BACKEND_COMPILE_EVENT)

    @property
    def total_compile_s(self) -> float:
        """Wall-clock spent in trace+lower+compile while active."""
        return sum(d for _, d in self.events)

    # -------------------------------------------------- tracked functions
    def track(self, name: str, fn) -> None:
        """Register a jitted callable whose compile count to pin."""
        self._tracked[name] = fn

    @staticmethod
    def cache_size(fn) -> int:
        """Compile-cache entries of one jitted callable."""
        return int(fn._cache_size())

    def counts(self) -> dict:
        return {name: self.cache_size(fn)
                for name, fn in self._tracked.items()}

    def assert_counts(self, expected: dict) -> dict:
        """Assert each tracked function compiled exactly N times.

        Returns the observed counts.
        """
        got = self.counts()
        for name, want in expected.items():
            assert got[name] == want, (f"{name}: {got[name]} compiled "
                                       f"programs, expected {want}")
        return got

    # ------------------------------------------------------------ summary
    def summary(self) -> dict:
        """JSON-safe snapshot for run logs / bench rows."""
        return {
            "n_backend_compiles": self.n_backend_compiles,
            "total_compile_s": round(self.total_compile_s, 4),
            "tracked": self.counts(),
        }

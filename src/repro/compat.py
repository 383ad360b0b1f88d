"""JAX runtime glue: the sweep engine's device mesh, the compile cache
location, and the runtime sanitizers.

``grid_mesh`` builds the 1-D all-local-devices mesh the sharded sweep
engine lays grid axes over. ``use_repo_compile_cache`` gives entry points
one persistent compilation cache at a fixed path inside the checkout.

The sanitizer half (``CompilationCounter``) wraps the jax runtime facility
the test suite and benchmark gates use to catch silent per-call
recompilation, the runtime face of the static linter's
(``repro.analysis.lint``) jit rules; tests run the transfer and leak
guards (``jax.transfer_guard``, ``jax.checking_leaks``) directly.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# <repo>/.jax_cache: a fixed path, so the next run of the same checkout finds
# what this one compiled
REPO_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_repo_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in ``<repo>/.jax_cache``,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one (JAX reads that itself).
    Returns the directory in use. Entry points call this before they
    compile; library code never does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_COMPILE_CACHE)
    return REPO_COMPILE_CACHE


def grid_mesh(axis: str = "grid", devices: Optional[Sequence] = None) -> Optional[Mesh]:
    """1-D mesh over all local devices, or None on a single-device host.

    The None return is the signal consumers (sweep.run_grid_sharded) use to
    fall back to the plain single-device vmap path.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    if len(devs) <= 1:
        return None
    return Mesh(np.asarray(devs), (axis,))


# ----------------------------------------------------- runtime sanitizers --


# jax.monitoring has no unregister API, so a single process-wide listener is
# installed lazily and left in place; CompilationCounter reads deltas of the
# running total. The event fires once per real XLA backend compile and not
# on jit-cache hits, which is what makes "compiled exactly once per shape"
# assertable.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_events = 0
_listener_installed = False


def _on_compile_event(event: str, duration: float, **kwargs) -> None:
    global _compile_events
    if event == _COMPILE_EVENT:
        _compile_events += 1


def _install_compile_listener() -> None:
    global _listener_installed
    if not _listener_installed:
        jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
        _listener_installed = True


def backend_compile_count() -> int:
    """Running total of XLA backend compiles seen since listener install."""
    _install_compile_listener()
    return _compile_events


class CompilationCounter:
    """Counts XLA backend compiles inside a ``with`` block.

    >>> with CompilationCounter() as c:
    ...     f(x)          # warm call
    >>> c.count           # 0 if f hit the jit cache, >=1 if it recompiled
    """

    count: int = 0

    def __enter__(self) -> "CompilationCounter":
        _install_compile_listener()
        self._start = _compile_events
        self.count = 0
        return self

    def __exit__(self, *exc) -> bool:
        self.count = _compile_events - self._start
        return False

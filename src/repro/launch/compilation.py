"""Compilation: JAX's persistent cache, and a counter of compiles.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``examples/agentic_rl_e2e.py``, ``bench_scheduler --live``) call
:func:`enable_compile_cache` once before they compile anything.  Library
code and tests never call it, and importing this module does nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache goes to the fixed
``.jax_cache/`` at the repository root (git ignores it): the directory is
part of the cache key, so a path that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


class CompileCounter:
    """Counts the programs JAX lowers and hands to the backend while
    active.  Both count every new executable: ``compiled`` includes the
    programs a warm persistent cache serves, since JAX times the cache
    lookup and the compile as one event::

        with CompileCounter() as cc:
            step()
        assert cc.lowered == 0
    """

    LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.lowered = 0
        self.compiled = 0

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.LOWER_EVENT:
            self.lowered += 1
        elif event == self.COMPILE_EVENT:
            self.compiled += 1

    def __enter__(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_event)

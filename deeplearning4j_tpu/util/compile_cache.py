"""JAX's persistent compilation cache at a place that can be chosen from
outside.

Every compile seam of this stack compiles twice — once for real, once as the
cost plane's shadow lower (telemetry/cost.py) — and a process that starts
with no compiled code pays both for every bucket, prefill length and train
step. JAX's own persistent cache removes the second compile of the process
and every compile of the next one, provided the directory does not move: the
path is part of what a later process has to find again.

Entry points call `enable_compile_cache()` before their first compile
(`chip_smoke.py`, `benchmarks/run.py`, `tools/smoke_*.py`,
`tools/loadgen.py`, `examples/*.py`). Importing the library does not turn
the cache on, and neither do the tests.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache():
    """Turn the persistent compilation cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX has read it already and
    nothing is set in code. Where it is not, the cache goes to the fixed
    `<checkout>/.jax_cache` (listed in `.gitignore`) — never a temporary
    name, a pid or a time, which no later process could find."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

"""Device (JAX/XLA) compute runtime.

64-bit support is required: routing keys are 64-bit hashes and integer SUM
accumulators need i64 range. TPUs emulate i64 with i32 limb pairs under XLA;
enabling x64 here (before any jax arrays exist) keeps key comparisons exact.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compile cache, placed from outside or at one fixed path: the
# directory is part of what a run must find again, so it is never a temp
# name. JAX reads JAX_COMPILATION_CACHE_DIR itself; only when that is unset
# does the code name a directory, inside the checkout. Worker subprocesses
# import this module too and so share it.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
# JAX's default keeps only programs that took a second or more to compile.
# On a v5e that was 5 of the 27 programs chip_smoke.py compiles (PERF.md,
# PR 21): the scatter steps and close reads take 0.06-0.5 s each and were
# all compiled again by a warm run. Keep everything.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def require_x64() -> None:
    """Idempotent pin for trace entry points living OUTSIDE this package.

    Importing ``arroyo_tpu.ops`` pins x64 as a side effect, but a module
    like ``engine/segment.py`` that jits traced code without ever touching
    a device kernel (a value/key/watermark-only chain) would otherwise
    trace under default 32-bit jax semantics: int64 inputs silently
    downcast, the uint64 routing hash truncates, and the first-batch
    verification fails into a permanent (and unexplained) interpreted
    fallback. Trace-safety rule LR304 requires every jit-root module to
    reach this pin before tracing."""
    jax.config.update("jax_enable_x64", True)

from .aggregate import (  # noqa: F401,E402
    AGG_KINDS,
    HostAggregator,
    acc_kinds_for,
    finalize_aggs,
)

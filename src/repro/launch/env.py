"""Launcher-level process environment the XLA runtime reads at import time
(tcmalloc preload detection, TF log noise, the persistent compilation
cache), applied by `launch/train.py`, `launch/serve.py` and `chip_smoke.py`
BEFORE `import jax`.

This module must therefore stay import-light: no jax, no repro modules that
pull jax in. Everything is pure env-dict manipulation so it is unit-testable
without touching the real process environment.

Escape hatch: pass `--no-env-tuning` on any launcher command line (peeked
from argv before argparse runs, because the tuning must land before the jax
import that argparse-time application would be too late for). It skips the
tuning only; the compilation cache is always wired.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Dict, List, Optional

# Well-known tcmalloc locations (Debian/Ubuntu package paths). Preloading
# tcmalloc avoids glibc-malloc contention on the host-side staging threads;
# we can only *detect and report* here — LD_PRELOAD must be set before the
# process starts to affect it, so the launcher exports it for children and
# prints a hint when the current process runs without it.
TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)

# Keep one-off large-allocation reports from spamming the log (the superstep
# staging buffers trip the default 1 GiB threshold constantly).
TCMALLOC_REPORT_THRESHOLD = "60000000000"

# The checkout root (src/repro/launch/env.py -> three levels up from src/).
CHECKOUT = Path(__file__).resolve().parents[3]


def find_tcmalloc() -> Optional[str]:
    """First existing well-known tcmalloc shared object, or None."""
    for p in TCMALLOC_PATHS:
        if os.path.exists(p):
            return p
    return None


def tuned_env(env: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Return the perf-hygiene mutations as a dict (pure; does not apply).

    * TF_CPP_MIN_LOG_LEVEL=4 — silence TF/XLA C++ info spam on the hot path
      (only if the user has not chosen a level).
    * TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD raised (if unset).
    * LD_PRELOAD set to a detected tcmalloc (if unset and one exists) so
      *child* processes get it; the current process is unaffected.

    XLA_FLAGS is left alone: jaxlib parses it once for every backend in the
    process and aborts on a flag it does not register, so a TPU-only flag
    there kills the host-side CPU client too.
    """
    env = dict(os.environ if env is None else env)
    out: Dict[str, str] = {}
    if "TF_CPP_MIN_LOG_LEVEL" not in env:
        out["TF_CPP_MIN_LOG_LEVEL"] = "4"
    if "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" not in env:
        out["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = TCMALLOC_REPORT_THRESHOLD
    tc = find_tcmalloc()
    if tc is not None and not env.get("LD_PRELOAD"):
        out["LD_PRELOAD"] = tc
    return out


def wants_tuning(argv: Optional[List[str]] = None) -> bool:
    """The escape hatch, peeked from raw argv (pre-argparse)."""
    argv = sys.argv if argv is None else argv
    return "--no-env-tuning" not in argv


# ---------------------------------------------------------------------------
# Persistent compilation cache (docs/DESIGN.md §Fault-tolerant streaming)
# ---------------------------------------------------------------------------

# jax's defaults skip sub-second compiles and small entries; zeroed, the
# small supersteps of tests and benchmarks are cached too
CACHE_THRESHOLDS = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                    "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}


def compilation_cache_env(env: Optional[Dict[str, str]] = None
                          ) -> Dict[str, str]:
    """The one rule for where compiled executables persist (pure; must land
    before `import jax`). A JAX_COMPILATION_CACHE_DIR the caller set is the
    cache, and no other directory is set. Otherwise the cache is the fixed
    `<checkout>/.jax_cache`: never a temporary or per-run path, since the
    directory is what a later run looks up. Thresholds the caller did not
    set are zeroed. A restarted run then pays a disk hit per (B, cohort)
    signature instead of a compile."""
    env = os.environ if env is None else env
    out = {k: v for k, v in CACHE_THRESHOLDS.items() if k not in env}
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        out["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    return out


def apply(env: Optional[Dict[str, str]] = None, *, echo: bool = False) -> Dict[str, str]:
    """Apply `tuned_env` to os.environ (or the given dict, for tests).
    Returns the mutations that were applied."""
    target = os.environ if env is None else env
    changes = tuned_env(dict(target))
    target.update(changes)
    if echo and changes:
        print("env tuning: " + " ".join(f"{k}={v}" for k, v in
                                        sorted(changes.items())),
              file=sys.stderr)
    if echo and find_tcmalloc() and "tcmalloc" not in os.environ.get(
            "LD_PRELOAD", ""):
        print("env tuning: tcmalloc present but not preloaded in THIS "
              "process (LD_PRELOAD only affects children); relaunch with "
              f"LD_PRELOAD={find_tcmalloc()} for host-thread malloc relief",
              file=sys.stderr)
    return changes


def apply_from_argv(argv: Optional[List[str]] = None) -> Dict[str, str]:
    """What launcher modules call at import time, before `import jax`: wire
    the compilation cache, and apply the tuning unless `--no-env-tuning` is
    on the command line."""
    changes = compilation_cache_env()
    os.environ.update(changes)
    if wants_tuning(argv):
        changes.update(apply(echo=False))
    return changes

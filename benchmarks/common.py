"""Shared helpers for the paper-reproduction benchmarks."""
from __future__ import annotations

import json
import os
import time

import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")
# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: a fixed place in the checkout, since the directory is part of
# what the cache is keyed on and a moving one never hits
COMPILE_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Called by the scripts that drive a chip (``chip_smoke.py`` and the
    benchmark entry points) before they compile anything, never on
    import.  ``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX keeps
    the cache and nothing is set here; otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def best_of(k: int, fn, *, warmup: int = 0) -> tuple[float, object]:
    """Best-of-``k`` wall clock of ``fn()`` via ``time.perf_counter``.

    The one timing idiom every benchmark here uses: ``warmup`` untimed
    calls (compile + cache warm), then ``k`` timed calls, reporting the
    *minimum* — the run least disturbed by the host.  ``fn`` must block
    until its device work is done (``jax.block_until_ready``).  Returns
    ``(best_seconds, last_result)``.
    """
    if k < 1:
        raise ValueError("best_of needs k >= 1")
    result = None
    for _ in range(warmup):
        result = fn()
    best = float("inf")
    for _ in range(k):
        t0 = time.perf_counter()
        result = fn()
        best = min(best, time.perf_counter() - t0)
    return best, result


def interleaved_best_of(k: int, fn_a, fn_b) -> tuple[float, float]:
    """Best-of-``k`` for two variants, alternating a/b each round.

    Interleaving exposes both variants to the same thermal / scheduler
    drift, so their *ratio* is meaningful even when absolute times are
    not (the machine-relative comparisons the CI gates use).  Callers
    warm both variants up first.  Returns ``(best_a, best_b)``.
    """
    if k < 1:
        raise ValueError("interleaved_best_of needs k >= 1")
    best_a = best_b = float("inf")
    for _ in range(k):
        t0 = time.perf_counter()
        fn_a()
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a, best_b


def save_result(name: str, payload: dict) -> str:
    out = os.path.join(RESULTS_DIR, "benchmarks")
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def prediction_mse(data, w, on: str = "test") -> float:
    """Label-prediction MSE of node-wise weights w (Table 1 metric)."""
    x = np.asarray(data.x)
    y = np.asarray(data.y)
    sm = np.asarray(data.sample_mask) > 0
    lm = np.asarray(data.labeled_mask) > 0
    if on == "train":
        keep = lm[:, None] & sm
    elif on == "test":
        keep = (~lm)[:, None] & sm
    else:
        keep = sm
    pred = np.einsum("vmn,vn->vm", x, np.asarray(w))
    return float(np.mean((pred[keep] - y[keep]) ** 2))

"""Faults planted under the timed path, each of which the comparison has
to catch: the benchmark's tests run a cell under each at a tiny size,
and ``bench/control.py --fault`` reads one at the cell's own size.

    unchanged    every Algorithm-1 step returns its state unchanged
    altered      each solve's answer altered where it is produced
    drop_delta   ``update_session`` drops its data delta, so a warm
                 solve answers the data as it stood before the request:
                 the stale answer a served request can give
"""
from __future__ import annotations

import contextlib
import dataclasses


@contextlib.contextmanager
def _patched(obj, name: str, value):
    """``obj.name`` (or ``obj[name]`` for a dict) set to ``value`` for
    the duration."""
    if isinstance(obj, dict):
        original = obj[name]
        obj[name] = value
    else:
        original = getattr(obj, name)
        setattr(obj, name, value)
    try:
        yield
    finally:
        if isinstance(obj, dict):
            obj[name] = original
        else:
            setattr(obj, name, original)


def unchanged():
    import repro.api.backends as backends

    def step(executor, prox, reg, lam, tau, sigma, w, u, **_):
        return w, executor.owned_duals(u)

    return _patched(backends, "engine_pd_step", step)


def altered():
    import repro.api.backends as backends
    solve_pallas = backends.BACKENDS["pallas"]

    def solve(*a, **k):
        res = solve_pallas(*a, **k)
        return dataclasses.replace(res, w=res.w.at[0].add(0.05))

    return _patched(backends.BACKENDS, "pallas", solve)


def drop_delta():
    from repro.serving import SolveService
    update = SolveService.update_session

    def update_session(self, session_id, delta=None, **kw):
        return update(self, session_id, **kw)

    return _patched(SolveService, "update_session", update_session)


FAULTS = {"unchanged": unchanged, "altered": altered,
          "drop_delta": drop_delta}

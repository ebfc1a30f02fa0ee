"""Batched multi-session solves: group by exec-sig, vmap once.

The paper's workload is *many* local datasets coupled over empirical
graphs; the serving twin of that is many tenants holding structurally
similar sessions.  Two sessions whose :attr:`PlanKey.exec_sig` match
(same loss/regularizer templates, same backend, same array shapes) can
run as a single ``jax.vmap``-ped dense-engine solve — stacked
``(w0, u0, data, lam)`` and even stacked *graph structure arrays* (the
dense engine treats src/dst/weights as traced operands), one XLA
executable, per-session residual certificates split back out.

:func:`solve_batch` is the entry point: it groups the requests
(:func:`group_requests`), runs each multi-member group through
:func:`repro.api.solver.solve_many`, falls back to the sequential
:meth:`SolveService.solve` for singleton groups, and keeps every
session/ledger side effect identical to the sequential path — warm
state updated, cold baselines respected, plan hits and the *batch*
executable's compile metered per session, and the group's measured
seconds and compiles shared among its responses.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax

from repro.api.solver import solve_many
from repro.engine import capped as _capped
from repro.serving.cache import PlanKey
from repro.serving.service import (SolveResponse, SolveService, _Clock,
                                   _fetch)


@dataclasses.dataclass(frozen=True)
class SolveRequest:
    """One pending solve: a session id plus the cold-start flag.

    ``queue_wait`` is the number of submissions the request sat behind
    in the serving queue (the queue's count-based clock; 0 for direct
    calls) — carried through to the response and its request event.
    """

    session_id: str
    cold: bool = False
    queue_wait: int = 0


def _as_request(req) -> SolveRequest:
    return req if isinstance(req, SolveRequest) else SolveRequest(str(req))


def group_requests(service: SolveService,
                   requests) -> list[list[SolveRequest]]:
    """Partition requests into vmap-able groups, preserving order.

    Group key = (exec_sig, config): exec-sig equality guarantees every
    traced array shape matches (so the problems stack), and config
    equality guarantees one loop shape.  Sessions with *different graph
    structures* land in the same group — structure arrays batch as
    traced operands.
    """
    groups: "OrderedDict[tuple, list[SolveRequest]]" = OrderedDict()
    for req in map(_as_request, requests):
        sess = service.session(req.session_id)
        key = PlanKey.for_problem(sess.problem, sess.config)
        groups.setdefault((key.exec_sig, sess.config), []).append(req)
    return list(groups.values())


def solve_batch(service: SolveService, requests,
                *, w_true=None) -> list[SolveResponse]:
    """Solve all ``requests`` (session ids or :class:`SolveRequest`),
    batching exec-sig-matched groups into single vmapped solves.

    Returns responses in request order.  Singleton groups take the
    sequential :meth:`SolveService.solve` path (a batch-of-one vmapped
    executable would pay an extra XLA trace for nothing).
    """
    del w_true  # reserved; serving solves carry no ground truth
    requests = [_as_request(r) for r in requests]
    groups: "OrderedDict[tuple, list[int]]" = OrderedDict()
    for i, req in enumerate(requests):
        sess = service.session(req.session_id)
        key = PlanKey.for_problem(sess.problem, sess.config)
        groups.setdefault((key.exec_sig, sess.config), []).append(i)
    responses: dict[int, SolveResponse] = {}
    for idxs in groups.values():
        if len(idxs) == 1:
            req = requests[idxs[0]]
            responses[idxs[0]] = service.solve(req.session_id,
                                               cold=req.cold,
                                               queue_wait=req.queue_wait)
        else:
            group = [requests[i] for i in idxs]
            for i, resp in zip(idxs, _solve_group(service, group)):
                responses[i] = resp
    return [responses[i] for i in range(len(requests))]


def _solve_group(service: SolveService,
                 group: list[SolveRequest]) -> list[SolveResponse]:
    """One vmapped solve for a multi-member exec-sig group."""
    sessions = [service.session(req.session_id) for req in group]
    cfg = sessions[0].config
    B = len(group)

    clock = _Clock()
    # per-session plan lookups meter the *batch* executable signature:
    # a vmapped executable over B problems is a different XLA trace
    # than the singleton one, shared by the whole group — the first
    # lookup that finds it new reports the compile
    batch_sig = ("batch", B) + PlanKey.for_problem(
        sessions[0].problem, cfg).exec_sig
    lookups = [service._plan(sess.problem, cfg, sig=batch_sig)
               for sess in sessions]

    problems = [sess.problem for sess in sessions]
    warms = [sess.w is not None and not req.cold
             for sess, req in zip(sessions, group)]
    # the stacked buffers are donated on TPU/GPU: warm starts are copies
    w0s, u0s = zip(*(
        service._warm_state(sess.w, sess.u, problem) if warm
        else (None, None)
        for sess, problem, warm in zip(sessions, problems, warms)))
    results = solve_many(problems, cfg, w0s=list(w0s), u0s=list(u0s))
    facts = [_fetch(result) for result in results]
    jax.block_until_ready(results[-1].w)
    timing = clock.read()

    iterations = int(results[0].diagnostics.get(
        "iterations", _capped(cfg.num_iters, cfg.metric_every)))
    responses = []
    for sess, req, result, fact, warm, (plan, hit, compiled) in zip(
            sessions, group, results, facts, warms, lookups):
        sess.w, sess.u = result.w, result.u
        sess.solves += 1
        cold_ref = sess.cold_iterations if warm else None
        if not warm:
            sess.cold_iterations = iterations
        led = service.ledger(sess.tenant)
        led.requests += 1
        led.record_solve(cache_hit=hit, compiled=compiled,
                         iterations=iterations, cold_ref=cold_ref)
        responses.append(service._response(
            sess, result, fact, timing.share(B, first=not responses),
            warm=warm, cache_hit=hit, compiled=compiled,
            iterations=iterations, queue_wait=req.queue_wait,
            batch_width=B))
    return responses

"""Engine recovery ladder: run a distributed job to completion under
injected server crashes.

Three rungs, cheapest first (the r-fold map replication is an erasure code
— see :mod:`repro.core.degraded`):

1. **decode-around** — every row lost with the crashed servers still has a
   surviving replica owner (guaranteed for any f <= r-1 failures per
   multicast group), so a degraded plan re-routes stage 1 around the dead
   servers and NOTHING is re-mapped;
2. **partial re-map** — subfiles that lost ALL r owners (orphans) are
   re-mapped on survivors and injected into stage 1 as an additive table
   patch; everything else still decodes around;
3. **bounded-retry restart** — unrecoverable attempts (every server dead,
   or orphans with ``allow_partial_remap=False``) burn one restart from the
   shared :class:`repro.resilience.backoff.RestartBudget` (jittered
   exponential backoff — the same accountant as the trainer's
   checkpoint/resume loop) and re-enter the ladder on the injector's next
   attempt schedule.

Every rung produces outputs BIT-IDENTICAL to the failure-free run: degraded
stage-1 tables reconstruct exactly the failure-free tables (repair reads
are raw replica rows; orphan patches are exact re-mapped values), and
map/stage-2/reduce run the same per-device programs as the fused pipeline.
The 8-device driver and ``benchmarks/faults_bench.py`` assert this for both
plan families.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.degraded import DegradedPlan, build_patch, compile_degraded_plan
from ..core.coded_collectives import device_plan_tables, shuffle_device_body
from ..core.params import SchemeParams
from ..obs import metrics as obs_metrics
from ..obs.bytes import degraded_rack_bytes, record_rack_bytes
from ..resilience.backoff import RestartBudget
from ..resilience.faults import FaultSpec

RECOVERY_RUNGS = ("none", "decode_around", "partial_remap", "restart")


class UnrecoverableFailure(RuntimeError):
    """An attempt cannot be salvaged by degraded execution (every server
    dead, or orphaned subfiles with partial re-map disabled) — escalates to
    the restart rung."""


@dataclasses.dataclass(frozen=True)
class RecoveryReport:
    """How a faulted job actually finished: which ladder rung produced the
    returned outputs, which servers were dead during the successful
    attempt, how many subfiles were re-mapped, and the restart accounting
    (delays are the recorded backoff schedule, slept only if the
    :class:`FaultSpec` carried a sleeper)."""
    rung: str
    failed: Tuple[int, ...]
    n_remapped: int
    restarts: int
    backoff_delays: Tuple[float, ...]
    attempts: int


@functools.lru_cache(maxsize=32)
def _degraded_executable(job, dplan: DegradedPlan, mesh: Mesh,
                         combine_impl: str):
    """One jitted shard_map program for a degraded attempt: per-device map
    -> crash mask -> degraded unicast shuffle (+ orphan patch) -> reduce.

    Identical per-device structure to the failure-free fused pipeline (same
    vmap'd map, same device body, same reduce), so surviving devices
    compute bit-identical rows.  The crash mask zeroes the failed devices'
    map outputs INSIDE the program — the replacement worker at that mesh
    coordinate participates in the collective with empty memory, and tests
    poison those values to prove nothing flows out of dead state.  Cached
    per (job, degraded-plan, mesh) like the fused executable.
    """
    p = dplan.params
    plan = dplan.plan
    tables = device_plan_tables(plan)
    alive = np.ones((p.P, p.Kr), dtype=bool)
    for s in dplan.failed:
        alive[s // p.Kr, s % p.Kr] = False
    alive_t = jnp.asarray(alive)

    def device_fn(subs, patch):      # [1, n_loc, ...], [1, n_layer, qr, d]
        i = jax.lax.axis_index("rack")
        j = jax.lax.axis_index("server")
        vals = jax.vmap(lambda s: job.map_fn(s, p.Q))(subs[0])  # [n_loc,Q,d]
        vals = jnp.where(alive_t[i, j], vals, jnp.zeros_like(vals))
        rows = shuffle_device_body(vals, plan, tables, "unicast",
                                   combine_impl,
                                   patch=patch[0].astype(vals.dtype))
        return jax.vmap(job.reduce_fn, in_axes=1)(rows)[None]   # [1,q_srv,*]

    # check_vma off for pallas: see coded_collectives.hybrid_shuffle
    fn = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(P(("rack", "server")), P(("rack", "server"))),
                       out_specs=P(("rack", "server")),
                       check_vma=combine_impl != "pallas")
    return jax.jit(fn)


def _degraded_attempt(job, subfiles: np.ndarray, p: SchemeParams, mesh: Mesh,
                      failed: Tuple[int, ...], faults: FaultSpec, *,
                      combine_impl: str, placement, scheme_family: str):
    """Rungs 1-2: degraded execution around ``failed``; returns
    (outputs [K, q_srv, d_out], degraded plan, n_remapped, rung)."""
    from .engine import pack_local_subfiles, put_per_device
    if len(failed) >= p.K:
        raise UnrecoverableFailure(
            f"all {p.K} servers failed; no survivors to recover on")
    perm = getattr(placement, "perm", placement)
    dplan = compile_degraded_plan(p, failed, family=scheme_family, perm=perm)
    n_remap = int(dplan.orphan_subfiles.size)
    if n_remap and not faults.allow_partial_remap:
        raise UnrecoverableFailure(
            f"{n_remap} subfiles lost all {p.r} owners and partial re-map "
            f"is disabled")
    local_subs = put_per_device(pack_local_subfiles(subfiles, dplan.base),
                                mesh)
    q_rack = p.Q // p.P
    if n_remap:
        # rung 2: re-map ONLY the orphaned subfiles on survivors (the
        # re-map work the sim prices) and inject them as a stage-1 patch
        remap = jax.jit(jax.vmap(lambda s: job.map_fn(s, p.Q)))
        orphan_vals = np.asarray(
            remap(jnp.asarray(np.asarray(subfiles)[dplan.orphan_subfiles])))
        patch = build_patch(dplan, orphan_vals)
    else:
        patch = np.zeros((p.K, p.subfiles_per_layer, q_rack, job.d),
                         dtype=np.float32)
    exe = _degraded_executable(job, dplan, mesh, combine_impl)
    out = exe(local_subs, put_per_device(patch, mesh))
    rung = "partial_remap" if n_remap else "decode_around"
    return out, dplan, n_remap, rung


def run_with_recovery(job, subfiles: np.ndarray, p: SchemeParams, mesh: Mesh,
                      faults: FaultSpec, *, multicast: str = "unicast",
                      combine_impl: str = "xla", placement=None,
                      scheme_family: str = "binomial"):
    """Execute ``job`` under the fault schedule, climbing the recovery
    ladder until an attempt completes; returns the
    :class:`repro.mapreduce.engine.JobResult` with ``.recovery`` filled.

    ``p`` must already carry the effective r (the engine resolves the
    override before dispatching here).  Attempt k applies
    ``faults.injector.events_for_attempt(k)``; an attempt with no scheduled
    events runs the plain failure-free path (that is how transient failures
    resolve after a restart).
    """
    from .engine import JobResult, assemble_outputs, run_job_distributed
    budget = RestartBudget(max_restarts=faults.max_restarts,
                           policy=faults.backoff, seed=faults.seed,
                           sleep=faults.sleep)
    attempt = 0
    while True:
        events = faults.injector.events_for_attempt(attempt)
        failed = tuple(sorted({s for e in events for s in e.servers}))
        try:
            if not failed:
                res = run_job_distributed(
                    job, subfiles, p, mesh, fused=True, multicast=multicast,
                    combine_impl=combine_impl, placement=placement,
                    scheme_family=scheme_family)
                rung = "none" if attempt == 0 else "restart"
                _record_rung(rung, scheme_family)
                res.recovery = RecoveryReport(
                    rung, failed, 0, budget.restarts, tuple(budget.delays),
                    attempt + 1)
                return res
            out, dplan, n_remap, rung = _degraded_attempt(
                job, subfiles, p, mesh, failed, faults,
                combine_impl=combine_impl, placement=placement,
                scheme_family=scheme_family)
            final = assemble_outputs(out, dplan.plan)
            from ..core.costs import hybrid_cost, hybrid_resolvable_cost
            from ..core.plan_registry import scheme_of_family
            c = (hybrid_resolvable_cost(p) if scheme_family == "resolvable"
                 else hybrid_cost(p))
            scheme = scheme_of_family(scheme_family)
            # the degraded attempt's ACTUAL wire bytes (unicast repair
            # schedule + orphan redistribution), not the failure-free
            # closed form — what a recovery really moved
            rb = record_rack_bytes(degraded_rack_bytes(dplan, job.d),
                                   scheme, scheme_family,
                                   layer="engine_degraded")
            _record_rung(rung, scheme_family)
            res = JobResult(final, c.intra, c.cross, scheme,
                            intra_rack_bytes=rb.intra_total,
                            cross_rack_bytes=rb.cross_total)
            res.recovery = RecoveryReport(
                rung, failed, n_remap, budget.restarts,
                tuple(budget.delays), attempt + 1)
            return res
        except UnrecoverableFailure as e:
            budget.next_restart(e)    # raises e when the budget is spent
            obs_metrics.counter(
                "engine_restarts_total",
                "restart-budget consumption of the recovery ladder").inc(
                    family=scheme_family)
            attempt += 1


def _record_rung(rung: str, family: str) -> None:
    obs_metrics.counter(
        "recovery_rung_total",
        "recovery-ladder rung that produced the returned outputs").inc(
            rung=rung, family=family)


__all__ = ["RecoveryReport", "RECOVERY_RUNGS", "UnrecoverableFailure",
           "run_with_recovery"]

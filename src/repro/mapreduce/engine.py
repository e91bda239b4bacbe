"""Executable MapReduce engine over jnp arrays.

A job maps each subfile to a dense intermediate tensor V_i in R^{Q x d}
(one length-d value per reduce key), shuffles so the reducer of key q holds
{V_i[q] : all i}, and reduces per key.  The engine runs under any of the
paper's three shuffle schemes and reports the paper-metric communication
costs alongside the (bit-exact) results.

Two execution paths:
  * run_job            — single-device: dense shuffle oracle + analytic costs
  * run_job_distributed — multi-device: the real two-stage shard_map shuffle
    of :mod:`repro.core.coded_collectives` over a ('rack','server') mesh.
    Default ``fused=True`` runs map -> pack -> shuffle -> reduce as ONE
    jitted, device-resident shard_map program: each device maps only its own
    n_loc assigned subfiles, packs via on-device gathers from the plan's
    cached index-table constants, shuffles, and reduces its own keys — zero
    host transfers between phases.  ``fused=False``
    keeps the legacy host-round-trip path (single-device map of all N, host
    NumPy packing, re-upload) for comparison — see
    ``benchmarks/pipeline_bench.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.assignment import (coded_assignment, hybrid_assignment,
                               uncoded_assignment)
from ..core.coded_collectives import (HybridShufflePlan,
                                      compile_hybrid_plan,
                                      device_plan_tables,
                                      hybrid_shuffle, pack_local_values,
                                      reduce_output_keys,
                                      reduce_ready_order,
                                      shuffle_device_body)
from ..core.costs import (coded_cost, hybrid_cost, hybrid_resolvable_cost,
                          uncoded_cost)
from ..core.params import SchemeParams
from ..core.plan_registry import scheme_of_family
from ..core.resolvable import resolvable_assignment
from ..core.shuffle_plan import count_plan, make_plan
from ..obs.bytes import (plan_rack_bytes, reconcile, record_rack_bytes,
                         record_shuffle_slots)
from ..obs.metrics import refresh_cache_metrics
from ..obs.tracing import get_tracer, op_stages, spans_from_phase_timings

# the jax.named_scope names of the fused program's stages, in pipeline order
# (map and reduce here; the shuffle's in shuffle_device_body; a job's
# reduce_fn may open reducer_sort inside reduce, as terasort_job does)
FUSED_STAGES = ("map", "stage1", "encode", "decode", "stage2", "reduce",
                "reducer_sort")


@dataclasses.dataclass(frozen=True)
class MapReduceJob:
    name: str
    d: int                                    # payload width per (key, subfile)
    map_fn: Callable[[jax.Array, int], jax.Array]   # subfile data -> [Q, d]
    reduce_fn: Callable[[jax.Array], jax.Array]     # [N, d] -> [d_out]
    # a job whose map emits fixed-capacity blocks of record slots: the
    # input's shape -> (record slots, padding slots) a job ships
    record_slots: Callable[[tuple], Tuple[int, int]] | None = None


@dataclasses.dataclass
class JobResult:
    outputs: jax.Array                        # [Q, d_out] final reduced values
    intra_cost: float                         # paper metric (kv pairs)
    cross_cost: float
    scheme: str
    # filled by the recovery ladder when the job ran under injected faults
    # (repro.mapreduce.recovery.RecoveryReport); None on failure-free runs
    recovery: object | None = None
    # rack-level byte accounting in value-units (pairs x payload width d),
    # paper-metric counting, derived from the ACTUAL compiled plan and
    # reconciled against the closed forms (repro.obs.bytes) — the same
    # fields JobStats carries on the sim side
    intra_rack_bytes: float = 0.0
    cross_rack_bytes: float = 0.0
    # measured-wall-clock blame components (repro.obs.blame schema) from
    # the run's engine_phase trace spans, one per traced phase (upload,
    # assemble and account included); None when tracing is disabled.
    # Components sum to the total traced phase wall (the engine-side
    # exactness law) — the fused device program stays one indivisible
    # 'map_shuffle_reduce' entry rather than a fabricated per-phase split
    blame: Dict[str, float] | None = None


def _validate_mesh(mesh: Mesh, p: SchemeParams) -> None:
    """Fail fast (and legibly) on a mesh that does not realize the scheme's
    (P racks) x (Kr servers) grid — a mismatch otherwise surfaces deep
    inside shard_map as an opaque XLA shape error."""
    names = tuple(mesh.axis_names)
    if "rack" not in names or "server" not in names:
        raise ValueError(
            f"mesh must have axes ('rack', 'server'); got {names!r}")
    shape = dict(mesh.shape)
    if shape["rack"] != p.P or shape["server"] != p.Kr:
        raise ValueError(
            f"mesh shape (rack={shape['rack']}, server={shape['server']}) "
            f"does not match SchemeParams: need rack=P={p.P}, "
            f"server=Kr={p.Kr} (K={p.K} servers in {p.P} racks)")


def _assignment_for(params: SchemeParams, scheme: str):
    return {"uncoded": uncoded_assignment,
            "coded": coded_assignment,
            "hybrid": hybrid_assignment,
            "hybrid_resolvable": resolvable_assignment}[scheme](params)


def map_phase(job: MapReduceJob, subfiles: jax.Array, Q: int) -> jax.Array:
    """[N, ...] subfile data -> V[N, Q, d]."""
    return jax.vmap(lambda s: job.map_fn(s, Q))(subfiles)


def run_job(job: MapReduceJob, subfiles: jax.Array, params: SchemeParams,
            scheme: str = "hybrid", count_messages: bool = False) -> JobResult:
    """Single-device execution with the paper's communication accounting.

    ``count_messages=True`` counts the explicit schedule (slow, exact);
    otherwise the closed forms of Props 1-2 / Thm III.1 are used — the two
    are proven equal in tests.
    """
    V = map_phase(job, subfiles, params.Q)              # [N, Q, d]
    outputs = jax.vmap(job.reduce_fn, in_axes=1)(V)     # [Q, d_out]
    if count_messages:
        a = _assignment_for(params, scheme)
        counts = count_plan(make_plan(a), params)
        intra, cross = float(counts.intra), float(counts.cross)
    else:
        cost_fn = {"uncoded": uncoded_cost, "coded": coded_cost,
                   "hybrid": hybrid_cost,
                   "hybrid_resolvable": hybrid_resolvable_cost}[scheme]
        c = cost_fn(params)
        intra, cross = c.intra, c.cross
    return JobResult(outputs, intra, cross, scheme,
                     intra_rack_bytes=intra * job.d,
                     cross_rack_bytes=cross * job.d)


def pack_local_subfiles(subfiles: np.ndarray,
                        plan: HybridShufflePlan) -> np.ndarray:
    """Distribute raw subfile data into the fused pipeline's per-device
    layout: [K, n_loc, ...] — device (i, j)'s rows are ITS assigned subfiles
    in ``plan.local_subfiles[i, j]`` order (the only host-side step of the
    fused path; everything after lives on device)."""
    p = plan.params
    return np.asarray(subfiles)[plan.local_subfiles.reshape(p.K, -1)]


def put_per_device(x: np.ndarray, mesh: Mesh) -> jax.Array:
    """Upload a host [K, ...] per-device array straight to its devices: row
    (i*Kr + j) goes to mesh device (i, j) only, so no single device ever
    holds the whole (r-fold replicated) input."""
    return jax.device_put(x, NamedSharding(mesh, P(("rack", "server"))))


def assemble_outputs(out: jax.Array, plan: HybridShufflePlan) -> jax.Array:
    """[K, Q/K, d_out] per-server reduce rows -> [Q, d_out] in global key
    order, derived explicitly from :func:`reduce_output_keys` (row m of the
    flattened output holds key ``keys.ravel()[m]``, which is m only for the
    default contiguous partition)."""
    keys = reduce_output_keys(plan)
    flat = out.reshape(out.shape[0] * out.shape[1], -1)
    order = np.argsort(keys.reshape(-1), kind="stable")
    if isinstance(out.sharding, NamedSharding):
        # a gather across the mesh-sharded row axis: name where the result
        # lives (replicated), as an Explicit-axis mesh requires
        return flat.at[order].get(
            out_sharding=NamedSharding(out.sharding.mesh, P()))
    return flat[order]


@functools.lru_cache(maxsize=64)
def _fused_executable(job: MapReduceJob, plan: HybridShufflePlan, mesh: Mesh,
                      multicast: str, combine_impl: str):
    """Compile the end-to-end device-resident pipeline for (job, plan, mesh):
    ONE jitted shard_map program running map, pack, two-stage shuffle and
    reduce with no host round-trip.

    The cache keys on the job OBJECT (its map/reduce closures compare by
    identity, standard jit semantics) — reuse one job instance across calls
    to hit the compiled executable; a fresh factory call recompiles.
    Intermediates of the fused program are XLA-managed and never
    materialize host-side at all.  The packed input is not donated: no
    output has its shape, so XLA could alias nothing (it warns on the chip
    and copies on the CPU)."""
    p = plan.params
    tables = device_plan_tables(plan)       # on-device constants, plan-cached

    def device_fn(subs):                    # [1, n_loc, ...subfile dims]
        with jax.named_scope("map"):
            vals = jax.vmap(lambda s: job.map_fn(s, p.Q))(subs[0])
        rows = shuffle_device_body(vals, plan, tables, multicast,
                                   combine_impl)                # [N,q_srv,d]
        with jax.named_scope("reduce"):
            return jax.vmap(job.reduce_fn, in_axes=1)(rows)[None]

    # check_vma off for pallas: see coded_collectives.hybrid_shuffle
    fn = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(P(("rack", "server")),),
                       out_specs=P(("rack", "server")),
                       check_vma=combine_impl != "pallas")
    return jax.jit(fn)


# (fused executable, argument shape, dtype) -> the argument's
# ShapeDtypeStruct with its sharding, recorded at the first call of each, for
# fused_op_stages; at most as many as _fused_executable caches
_FUSED_CALLS: Dict[tuple, jax.ShapeDtypeStruct] = {}
# the compiled HLO text of each recorded program, by the same key
_FUSED_TEXTS: Dict[tuple, str] = {}


def _record_fused_call(exe, arg: jax.Array) -> None:
    key = (exe, arg.shape, arg.dtype)
    if key not in _FUSED_CALLS:
        if len(_FUSED_CALLS) >= _fused_executable.cache_info().maxsize:
            del _FUSED_CALLS[next(iter(_FUSED_CALLS))]
        _FUSED_CALLS[key] = jax.ShapeDtypeStruct(arg.shape, arg.dtype,
                                                 sharding=arg.sharding)


def _fused_text(key: tuple, spec: jax.ShapeDtypeStruct) -> str:
    """The compiled HLO text of one recorded program, compiled once."""
    if key not in _FUSED_TEXTS:
        for old in [k for k in _FUSED_TEXTS if k not in _FUSED_CALLS]:
            del _FUSED_TEXTS[old]
        _FUSED_TEXTS[key] = key[0].lower(spec).compile().as_text()
    return _FUSED_TEXTS[key]


def fused_op_stages() -> Dict[str, str]:
    """``{op key: stage}`` over every fused executable called so far: which
    of :data:`FUSED_STAGES` (or joint ``"a+b"`` label) each op of the
    compiled programs belongs to, keyed by :func:`repro.obs.tracing.op_key`
    so that a device trace's op names look it up.  An op key found in two
    different programs is left out.  Compiles each program again, once, to
    read its HLO text: for readers of a trace, never on the job path."""
    texts = {_fused_text(key, spec) for key, spec in _FUSED_CALLS.items()}
    table: Dict[str, str] = {}
    twice: set = set()
    for text in texts:
        for key, stage in op_stages(text, FUSED_STAGES).items():
            if key in table:
                twice.add(key)
            table[key] = stage
    return {k: v for k, v in table.items() if k not in twice}


def _blame_from_spans(events, cost) -> Dict[str, float] | None:
    """Fold one run's ``engine_phase`` trace spans into blame components
    (:mod:`repro.obs.blame` schema).  Host phases map directly; a measured
    legacy ``shuffle`` wall is split ``shuffle_cross`` / ``shuffle_intra``
    by the scheme's closed-form unit ratio (the same convention as
    :func:`repro.obs.blame.blame_from_phase_timings`); the fused device
    program is kept whole under ``map_shuffle_reduce``.  Returns None when
    no spans were traced (tracing disabled)."""
    phases: Dict[str, float] = {}
    for ev in events:
        if ev.kind == "engine_phase" and ev.dur is not None:
            phases[ev.phase] = phases.get(ev.phase, 0.0) + float(ev.dur)
    if not phases:
        return None
    comps: Dict[str, float] = {}
    for k in ("plan_compile", "map", "pack", "upload", "reduce",
              "map_shuffle_reduce", "assemble", "account"):
        if k in phases:
            comps[k] = phases[k]
    if "shuffle" in phases:
        tot = cost.intra + cost.cross
        frac = cost.cross / tot if tot > 0 else 0.5
        comps["shuffle_cross"] = phases["shuffle"] * frac
        comps["shuffle_intra"] = phases["shuffle"] * (1.0 - frac)
    return comps


def run_job_distributed(job: MapReduceJob, subfiles: np.ndarray,
                        params: SchemeParams, mesh: Mesh,
                        r: int | None = None, *, fused: bool = True,
                        multicast: str = "unicast",
                        combine_impl: str = "xla",
                        placement: object | None = None,
                        scheme_family: str = "binomial",
                        faults: object | None = None) -> JobResult:
    """Multi-device execution: real all_to_all shuffle (hybrid scheme,
    general map-replication r in [1, P]).

    ``scheme_family`` selects the registered plan compiler: ``'binomial'``
    (the paper's construction) or ``'resolvable'`` (the SPC design of
    :mod:`repro.core.resolvable`, feasible at K far beyond the binomial
    divisibility wall — see docs/scaling.md).  Every downstream stage is
    family-agnostic: the fused executable caches on the plan object, and
    costs come from the family's closed form.

    ``mesh`` must have axes ('rack', 'server') with sizes (P, Kr).  Each
    device maps only ITS assigned subfiles (with r-fold replication across
    racks), shuffles via the two-stage hybrid schedule, and reduces its own
    keys.  ``r`` overrides ``params.r`` (the knob for sweeping the paper's
    computation/communication tradeoff curve).  Returns outputs identical
    to :func:`run_job` (asserted in tests).

    ``fused=True`` (default) runs the whole map->pack->shuffle->reduce chain
    as one jitted device-resident program (zero inter-phase host transfers);
    ``fused=False`` is the legacy path: dense single-device map of ALL N
    subfiles, host-side packing, re-upload, then the shuffle.  ``multicast``
    and ``combine_impl`` are forwarded to the shuffle (coded multicast
    packets and the Pallas f(.) kernels — see
    :func:`repro.core.coded_collectives.shuffle_device_body`).

    ``placement`` runs the job under a Section-IV locality-optimized layout:
    a :class:`repro.placement.PlacementResult` (or a bare slot permutation)
    whose perm decides which subfile each device maps — the shuffle index
    tables are permutation-invariant, so outputs are unchanged while each
    device's map inputs become the placement's (the real-cluster analogue of
    the simulator's fetch-traffic bridge).

    ``faults`` (a :class:`repro.resilience.faults.FaultSpec`) runs the job
    under injected server crashes through the recovery ladder of
    :mod:`repro.mapreduce.recovery` — decode-around, partial re-map, then
    bounded-retry restart — and fills ``JobResult.recovery``; outputs stay
    bit-identical to the failure-free run.
    """
    p = params if r is None or r == params.r else \
        dataclasses.replace(params, r=r)
    _validate_mesh(mesh, p)
    if faults is not None:
        from .recovery import run_with_recovery
        res = run_with_recovery(job, subfiles, p, mesh, faults,
                                multicast=multicast,
                                combine_impl=combine_impl,
                                placement=placement,
                                scheme_family=scheme_family)
        refresh_cache_metrics()
        return res
    perm = getattr(placement, "perm", placement)
    tracer = get_tracer()
    span_lo = len(tracer.events)
    with tracer.span("plan_compile", kind="engine_phase",
                     job=job.name, family=scheme_family):
        plan = compile_hybrid_plan(p, perm=perm, family=scheme_family)
    if fused:
        with tracer.span("pack", kind="engine_phase", job=job.name):
            local_subs = put_per_device(
                pack_local_subfiles(subfiles, plan), mesh)
        if tracer.enabled:          # a wait only a traced run pays for
            with tracer.span("upload", kind="engine_phase", job=job.name):
                jax.block_until_ready(local_subs)
        with tracer.span("map_shuffle_reduce", kind="engine_phase",
                         job=job.name, fused="true"):
            exe = _fused_executable(job, plan, mesh, multicast, combine_impl)
            _record_fused_call(exe, local_subs)
            out = exe(local_subs)                       # [K, q_srv, d_out]
            jax.block_until_ready(out)
    else:
        with tracer.span("map", kind="engine_phase", job=job.name):
            V = np.asarray(map_phase(job, jnp.asarray(subfiles), p.Q))
        with tracer.span("pack", kind="engine_phase", job=job.name):
            local = pack_local_values(V, plan)          # [K, n_loc, Q, d]
        with tracer.span("shuffle", kind="engine_phase", job=job.name):
            shuffled = hybrid_shuffle(jnp.asarray(local), plan, mesh,
                                      multicast, combine_impl)
            jax.block_until_ready(shuffled)
        with tracer.span("reduce", kind="engine_phase", job=job.name):
            # [K, N, q_srv, d]; rows ordered by reduce_ready_order
            out = jax.vmap(jax.vmap(job.reduce_fn, in_axes=1))(shuffled)
            jax.block_until_ready(out)
    with tracer.span("assemble", kind="engine_phase", job=job.name):
        final = assemble_outputs(out, plan)             # [Q, d_out]
        if tracer.enabled:
            jax.block_until_ready(final)
    with tracer.span("account", kind="engine_phase", job=job.name):
        scheme = scheme_of_family(scheme_family)
        c = (hybrid_resolvable_cost(p) if scheme_family == "resolvable"
             else hybrid_cost(p))
        # rack-level byte accounting off the ACTUAL compiled plan,
        # paper-metric counting, re-reconciled against the closed form on
        # every run
        rb = record_rack_bytes(plan_rack_bytes(plan, "coded", job.d),
                               scheme, scheme_family, layer="engine")
        reconcile(rb.intra_total, rb.cross_total, p, scheme, d=job.d,
                  check=False)
        if job.record_slots is not None:
            record_shuffle_slots(job.name,
                                 *job.record_slots(np.shape(subfiles)))
        # cache gauges stay current in snapshots without a manual pull
        refresh_cache_metrics()
    return JobResult(final, c.intra, c.cross, scheme,
                     intra_rack_bytes=rb.intra_total,
                     cross_rack_bytes=rb.cross_total,
                     blame=_blame_from_spans(tracer.events[span_lo:], c))


# ---------------------------------------------------------------------------
# Per-phase timing instrumentation (calibration feed for repro.sim)
# ---------------------------------------------------------------------------

def _best_of(fn: Callable[[], object], iters: int) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def measure_phase_timings(job: MapReduceJob, subfiles: np.ndarray,
                          params: SchemeParams, mesh: Mesh,
                          iters: int = 3) -> Dict[str, object]:
    """Measure REAL per-phase wall clock of the hybrid pipeline, in the row
    format :func:`repro.sim.cluster.calibrate` consumes.

    Phases are timed separately on warm jitted executables: plan compile
    (cold, LRU cache cleared), map (all N subfiles), host pack, distributed
    shuffle, and reduce.  ``work`` holds the value-unit conventions of
    :class:`repro.sim.cluster.CostModel`; the fitted beta is therefore a
    per-value-unit rate of THIS host — a calibration proxy, not a TPU claim
    (the simulator divides work across the K simulated servers).
    """
    from ..core.coded_collectives import plan_cache_clear

    p = params
    plan_cache_clear()
    t0 = time.perf_counter()
    plan = compile_hybrid_plan(p)
    compile_s = time.perf_counter() - t0

    subs_dev = jnp.asarray(subfiles)
    map_jit = jax.jit(lambda s: map_phase(job, s, p.Q))
    V_host = np.asarray(map_jit(subs_dev))                       # warm-up
    map_s = _best_of(lambda: np.asarray(map_jit(subs_dev)), iters)

    pack_s = _best_of(
        lambda: jnp.asarray(pack_local_values(V_host, plan)
                            ).block_until_ready(), iters)
    local_dev = jnp.asarray(pack_local_values(V_host, plan))

    shuf_jit = jax.jit(lambda v: hybrid_shuffle(v, plan, mesh))
    shuffled = shuf_jit(local_dev)
    shuffled.block_until_ready()                                 # warm-up
    shuffle_s = _best_of(
        lambda: shuf_jit(local_dev).block_until_ready(), iters)

    red_jit = jax.jit(jax.vmap(jax.vmap(job.reduce_fn, in_axes=1)))
    red_jit(shuffled).block_until_ready()                        # warm-up
    reduce_s = _best_of(
        lambda: red_jit(shuffled).block_until_ready(), iters)

    d = job.d
    row = {
        "work": {
            "map": float(p.N) * p.Q * d,
            "pack": float(p.K) * plan.local_subfiles.shape[-1] * p.Q * d,
            "reduce": float(p.N) * p.Q * d,
            "plan_compile": float(p.N),
        },
        "seconds": {"map": map_s, "pack": pack_s, "reduce": reduce_s,
                    "plan_compile": compile_s},
        "meta": {"K": p.K, "P": p.P, "Q": p.Q, "N": p.N, "r": p.r, "d": d,
                 "job": job.name, "shuffle_s": shuffle_s,
                 "backend": jax.default_backend()},
    }
    if get_tracer().enabled:        # device-timing spans for trace export
        spans_from_phase_timings(row)
    return row


def measure_calibration_grid(job_factory: Callable[[int], MapReduceJob],
                             mesh: Mesh, points: List[tuple],
                             iters: int = 3) -> List[Dict[str, object]]:
    """Run :func:`measure_phase_timings` over (params, d) points — enough
    rows for the affine per-phase fit of :func:`repro.sim.cluster.calibrate`
    to be overdetermined."""
    rows = []
    for params, d in points:
        job = job_factory(d)
        rng = np.random.default_rng(params.N)
        subs = rng.integers(0, 1 << 16,
                            size=(params.N, 256)).astype(np.int32)
        rows.append(measure_phase_timings(job, subs, params, mesh, iters))
    return rows

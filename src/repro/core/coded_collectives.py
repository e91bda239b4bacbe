"""Distributed realization of the Hybrid Coded MapReduce shuffle in JAX.

Two executable forms:

1. :func:`hybrid_shuffle` — a shard_map program over a ('rack', 'server')
   mesh performing the paper's two-stage shuffle with `jax.lax.all_to_all`:
   a cross-rack stage over the 'rack' axis, then an intra-rack stage over the
   'server' axis.  Works for ANY map-replication factor r in [1, P] (the
   paper's Sec. III construction; Sec. IV optimizes the r = 2 instance,
   still available as the :func:`hybrid_shuffle_r2` alias).  Each of the r
   replicas of a block sources 1/r of it, which achieves the receive-side
   optimum  QN/r * (1 - r/P) * r = QN(1 - r/P)  pair receptions per stage-1
   exchange on point-to-point links.

   Plan layout (general r): layer j's NP/K subfiles are grouped by the
   C(P, r) rack r-subsets, M = (NP/K)/C(P, r) subfiles per subset, in
   lexicographic subset order — the canonical *layer table*.  Rack i maps
   the C(P-1, r-1) subsets containing i.  For a destination rack z outside
   a subset T ∋ i, sender i contributes the share of T's M subfiles at slice
   [pos*M/r, (pos+1)*M/r) where pos = T.index(i): the r senders' shares are
   disjoint and cover T's block, so every layer-table row is received exactly
   once and `at[...].add` == `at[...].set`.

   Fidelity note (see docs/shuffle.md): the paper counts a multicast packet
   ONCE at the root switch, giving the stronger (QN/r)(1 - r/P)
   *switch-traversal* cost.  TPU ICI/DCN expose no multicast primitive, so
   the executable path realizes the receive-side optimum while the
   switch-traversal metric is reproduced bit-exactly by the schedule
   simulator (:mod:`repro.core.shuffle_plan`).  For SUM-reducible shuffles
   (gradient aggregation) the linear-combining gain *is* natively realized on
   the wire by reduce-scatter — see :mod:`repro.core.gradient_sync`.

2. :func:`plan_shuffle_reference` — a dense single-device oracle for
   validating the distributed outputs bit-exactly.

Plan compilation (:func:`compile_hybrid_plan`) builds all index tables with
vectorized NumPy construction — no per-element Python loops or
``list.index`` scans — and is memoized with an LRU cache keyed on the
(hashable, frozen) :class:`SchemeParams`, so recompiling a seen config is
O(1).  Cached plans are shared: treat their arrays as immutable.

Data model: intermediate values form V[N, Q, d] (subfile, key, payload);
reducer of key q needs q's value on ALL N subfiles.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from math import comb
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .assignment import hybrid_assignment, rack_subsets
from .params import SchemeParams
from .plan_registry import (HybridShufflePlan, get_plan_compiler,
                            plan_families, register_plan_compiler)


# ---------------------------------------------------------------------------
# Plan compilation: static index tables for the general-r hybrid shuffle
# ---------------------------------------------------------------------------
#
# The plan schema (HybridShufflePlan) and the family registry live in
# repro.core.plan_registry; this module registers the paper's binomial
# construction and hosts the family-agnostic executable paths.  The
# resolvable-design family is registered by repro.core.resolvable
# (imported at the bottom of this module).


@register_plan_compiler("binomial")
def _compile_hybrid_plan_impl(p: SchemeParams,
                              perm: Tuple[int, ...] | None = None
                              ) -> HybridShufflePlan:
    """Uncached binomial plan compilation for any r in [1, P] with r | M.

    All tables are built by vectorized index arithmetic on the structural
    (layer, subset, w) coordinates; cost is O(N + P^2 * C(P, r)).

    ``perm`` places subfile ``perm[slot]`` into each structural slot (the
    Section-IV locality degree of freedom); every positional table is
    perm-independent — only the subfile-id tables (``local_subfiles``,
    ``layer_subfiles``) change, so a locality-optimized plan shuffles
    byte-identically to the canonical one.
    """
    p.validate_hybrid()
    r = p.r
    M = p.M
    if M % r != 0:
        raise ValueError(f"executable hybrid plan needs r | M; M={M} r={r}")
    a = hybrid_assignment(p, perm=list(perm) if perm is not None else None)
    subsets = np.asarray(rack_subsets(p.P, r), dtype=np.int64)   # [n_sub, r]
    n_sub = subsets.shape[0]
    slot = np.asarray(a.meta["slot_of_subfile"], dtype=np.int64)  # [N, 3]

    share = M // r                         # rows each replica sources
    n_layer = p.subfiles_per_layer
    c_loc = comb(p.P - 1, r - 1)           # subsets containing a given rack
    c_pair = comb(p.P - 2, r - 1) if p.P >= 2 else 0   # i in T, z not in T
    n_loc = c_loc * M
    n_send = c_pair * share

    # subfile id of each structural slot: S[layer, subset, w]
    S = np.empty((p.Kr, n_sub, M), dtype=np.int64)
    S[slot[:, 0], slot[:, 1], slot[:, 2]] = np.arange(p.N)

    # rack-membership tables over subsets
    t_ids = np.repeat(np.arange(n_sub), r)
    member = np.zeros((p.P, n_sub), dtype=bool)
    member[subsets.ravel(), t_ids] = True              # member[i, t]: i in T_t
    pos_in = np.zeros((p.P, n_sub), dtype=np.int64)
    pos_in[subsets.ravel(), t_ids] = np.tile(np.arange(r), n_sub)

    # subsets containing each rack (ascending) and each subset's rank therein
    ts = np.nonzero(member)[1].reshape(p.P, c_loc)     # [P, c_loc]
    rank = np.zeros((p.P, n_sub), dtype=np.int64)
    rank[np.arange(p.P)[:, None], ts] = np.arange(c_loc)[None, :]

    # layer table is rack-independent; local tables are layer-independent:
    # store broadcast views to keep the [P, Kr, ...] interface without copies
    layer_table = np.broadcast_to(S.reshape(1, p.Kr, n_layer),
                                  (p.P, p.Kr, n_layer))
    local_subfiles = np.ascontiguousarray(
        S[:, ts, :].transpose(1, 0, 2, 3).reshape(p.P, p.Kr, n_loc))
    local_mask = np.broadcast_to(
        np.repeat(member, M, axis=1)[:, None, :], (p.P, p.Kr, n_layer))
    local_pos = np.broadcast_to(
        (ts[:, :, None] * M + np.arange(M)).reshape(p.P, 1, n_loc),
        (p.P, p.Kr, n_loc))

    cross_send_pos = np.zeros((p.P, p.Kr, p.P, n_send), dtype=np.int64)
    cross_recv_pos = np.zeros((p.P, p.Kr, p.P, n_send), dtype=np.int64)
    n_known = max(r - 1, 0)
    mcast_comp_pos = np.zeros((p.P, p.P, n_send, r), dtype=np.int64)
    mcast_comp_rack = np.zeros((p.P, p.P, n_send, r), dtype=np.int64)
    mcast_known_pos = np.zeros((p.P, p.P, n_send, n_known), dtype=np.int64)
    mcast_known_rack = np.zeros((p.P, p.P, n_send, n_known), dtype=np.int64)
    if n_send:
        subset_index = {tuple(T): t for t, T in enumerate(subsets.tolist())}
        off = np.arange(share)
        for i in range(p.P):
            for z in range(p.P):
                if z == i:
                    continue
                # i's share of every subset it maps that z does not
                t_snd = np.nonzero(member[i] & ~member[z])[0]    # [c_pair]
                cross_send_pos[i, :, z, :] = (
                    rank[i, t_snd, None] * M
                    + pos_in[i, t_snd, None] * share + off).reshape(-1)
                # where z's share of the subsets i lacks lands in the table
                t_rcv = np.nonzero(member[z] & ~member[i])[0]
                cross_recv_pos[i, :, z, :] = (
                    t_rcv[:, None] * M
                    + pos_in[z, t_rcv, None] * share + off).reshape(-1)
                # --- coded multicast component tables ----------------------
                # Packet block a of the i -> z stream realizes the multicast
                # group S = T ∪ {z} (T = t_snd[a]): component c serves
                # receiver z2 in S \ {i} with i's share of T_{z2} = S \ {z2}.
                # The components depend only on (S, w), so the packet i sends
                # every receiver of S is identical — a true multicast payload.
                for a, t in enumerate(t_snd):
                    S = tuple(sorted(subsets[t].tolist() + [z]))
                    rows = slice(a * share, (a + 1) * share)
                    for c, z2 in enumerate(x for x in S if x != i):
                        t2 = subset_index[tuple(x for x in S if x != z2)]
                        mcast_comp_pos[i, z, rows, c] = (
                            rank[i, t2] * M + pos_in[i, t2] * share + off)
                        mcast_comp_rack[i, z, rows, c] = z2
                # Receiver i decoding source s = z's stream: packet block a
                # covers T = t_rcv[a] (∋ s, ∌ i), group S = T ∪ {i}; the
                # known components are s's shares of T_{z2}, z2 in S\{s, i} —
                # all mapped locally at i since i ∈ T_{z2}.
                for a, t in enumerate(t_rcv):
                    S = tuple(sorted(subsets[t].tolist() + [i]))
                    rows = slice(a * share, (a + 1) * share)
                    for c, z2 in enumerate(x for x in S if x not in (z, i)):
                        t2 = subset_index[tuple(x for x in S if x != z2)]
                        mcast_known_pos[i, z, rows, c] = (
                            rank[i, t2] * M + pos_in[z, t2] * share + off)
                        mcast_known_rack[i, z, rows, c] = z2
    return HybridShufflePlan(p, local_subfiles, cross_send_pos, layer_table,
                             cross_recv_pos, local_mask, n_send, local_pos,
                             mcast_comp_pos, mcast_comp_rack,
                             mcast_known_pos, mcast_known_rack)


# ---------------------------------------------------------------------------
# Plan cache: configurable LRU with per-family introspection
# ---------------------------------------------------------------------------
#
# The cache maxsize is configurable (the multi-job scheduler of `repro.sim`
# charges plan-compile latency on cache miss, and sweeps want to bound or
# disable caching): set the REPRO_PLAN_CACHE_MAXSIZE env var before import,
# or call :func:`configure_plan_cache` at runtime.  Entries are keyed on
# (params, perm, family) — two families of the same (params, perm) are
# distinct plans — and hit/miss counters are kept per family so the
# scheduler's compile-charge accounting stays honest when it prices
# binomial vs resolvable candidates of one job.

PLAN_CACHE_MAXSIZE_ENV = "REPRO_PLAN_CACHE_MAXSIZE"
_PLAN_CACHE_DEFAULT_MAXSIZE = 128


class FamilyCacheInfo(NamedTuple):
    hits: int
    misses: int


class PlanCacheInfo(NamedTuple):
    """CacheInfo of the plan cache, extended with per-family counters
    (``families`` maps family name -> :class:`FamilyCacheInfo`; families
    never compiled are absent)."""
    hits: int
    misses: int
    maxsize: int | None
    currsize: int
    families: Dict[str, FamilyCacheInfo]


def _plan_cache_default_maxsize() -> int:
    raw = os.environ.get(PLAN_CACHE_MAXSIZE_ENV, "")
    try:
        return int(raw)
    except ValueError:
        return _PLAN_CACHE_DEFAULT_MAXSIZE


def _drop_device_tables() -> None:
    # device_plan_tables is defined later in the module (it needs the plan
    # type); guard for the import-time configure_plan_cache() call
    fn = globals().get("device_plan_tables")
    if fn is not None:
        fn.cache_clear()


def _compile_plan_dispatch(p: SchemeParams, perm: Tuple[int, ...] | None,
                           family: str) -> HybridShufflePlan:
    """The cached unit: registry dispatch on the full (params, perm, family)
    key."""
    return get_plan_compiler(family)(p, perm)


def configure_plan_cache(maxsize: int | None = None):
    """(Re)build the LRU plan cache with the given maxsize (``None`` -> the
    ``REPRO_PLAN_CACHE_MAXSIZE`` env var, falling back to 128).  Drops all
    cached plans (and their on-device table uploads — see
    :func:`plan_cache_clear`) and zeroes the per-family counters; returns
    the new cache wrapper."""
    global _PLAN_CACHE
    if maxsize is None:
        maxsize = _plan_cache_default_maxsize()
    _PLAN_CACHE = functools.lru_cache(maxsize=maxsize)(_compile_plan_dispatch)
    _FAMILY_STATS.clear()
    _drop_device_tables()
    return _PLAN_CACHE


_FAMILY_STATS: Dict[str, list] = {}   # family -> [hits, misses]
_PLAN_CACHE = configure_plan_cache()


def compile_hybrid_plan(p: SchemeParams,
                        perm: Sequence[int] | None = None,
                        family: str = "binomial") -> HybridShufflePlan:
    """LRU-cached plan compilation; repeated calls for a seen
    (:class:`SchemeParams`, perm, family) return the SAME plan object in
    O(1).  ``perm`` is the Section-IV slot permutation of a
    locality-optimized placement (``repro.placement``); None is the
    canonical identity layout.  ``family`` selects the registered plan
    compiler (see :mod:`repro.core.plan_registry`): ``'binomial'`` is the
    paper's Sec. III construction, ``'resolvable'`` the SPC resolvable
    design of :mod:`repro.core.resolvable`."""
    key_perm = None if perm is None else tuple(int(x) for x in perm)
    before = _PLAN_CACHE.cache_info().misses
    plan = _PLAN_CACHE(p, key_perm, family)
    missed = _PLAN_CACHE.cache_info().misses > before
    st = _FAMILY_STATS.setdefault(family, [0, 0])
    st[1 if missed else 0] += 1
    return plan


def plan_cache_info() -> PlanCacheInfo:
    """:class:`PlanCacheInfo` of the plan cache — the scheduler reads the
    per-family counters to account compile cost on miss."""
    info = _PLAN_CACHE.cache_info()
    fams = {f: FamilyCacheInfo(h, m) for f, (h, m) in
            sorted(_FAMILY_STATS.items())}
    return PlanCacheInfo(info.hits, info.misses, info.maxsize, info.currsize,
                         fams)


def plan_cache_clear() -> None:
    """Drop all cached plans AND their on-device index tables:
    :func:`device_plan_tables` keys on plan identity, so a cleared plan
    cache would otherwise pin every evicted plan (and its device arrays)
    alive inside the tables cache.  Also zeroes the per-family counters."""
    _PLAN_CACHE.cache_clear()
    _FAMILY_STATS.clear()
    _drop_device_tables()


# Back-compat: existing call sites treat compile_hybrid_plan as the
# lru_cache wrapper itself.
compile_hybrid_plan.cache_info = plan_cache_info    # type: ignore[attr-defined]
compile_hybrid_plan.cache_clear = plan_cache_clear  # type: ignore[attr-defined]


def compile_hybrid_plan_r2(p: SchemeParams) -> HybridShufflePlan:
    """Back-compat alias: the r = 2 instance of :func:`compile_hybrid_plan`
    (rejects other r, as the pre-general-r API did)."""
    if p.r != 2:
        raise ValueError("compile_hybrid_plan_r2 is the r = 2 special case; "
                         "use compile_hybrid_plan for general r")
    return compile_hybrid_plan(p)


# Back-compat name for the plan type (the r = 2 plan is just an instance).
HybridShufflePlanR2 = HybridShufflePlan


# ---------------------------------------------------------------------------
# Distributed execution (shard_map over ('rack', 'server'))
# ---------------------------------------------------------------------------

MULTICAST_MODES = ("unicast", "coded", "coded_xor")
COMBINE_IMPLS = ("xla", "pallas")


@dataclasses.dataclass(frozen=True, eq=False)
class DevicePlanTables:
    """The plan's index tables as on-device jnp constants (hoisted once per
    plan — see :func:`device_plan_tables`)."""
    send_pos: jax.Array          # [P, Kr, P, n_send]
    recv_pos: jax.Array          # [P, Kr, P, n_send]
    local_pos: jax.Array         # [P, Kr, n_loc]
    mcast_comp_pos: jax.Array    # [P, P, n_send, arity]
    mcast_comp_rack: jax.Array
    mcast_known_pos: jax.Array   # [P, P, n_send, arity-1]
    mcast_known_rack: jax.Array
    # stage-1 slot validity [P, P, n_send]; None = binomial's uniform rule
    cross_valid: Optional[jax.Array] = None


@functools.lru_cache(maxsize=128)
def device_plan_tables(plan: HybridShufflePlan) -> DevicePlanTables:
    """jnp views of a plan's index tables, transferred to device once and
    cached alongside the LRU'd plan (plans hash by identity, and
    :func:`compile_hybrid_plan` returns the same object per config, so a
    repeated shuffle never re-uploads its tables).

    The upload is forced OUTSIDE any active trace
    (``ensure_compile_time_eval``): the first call for a plan may happen
    inside a jitted caller (e.g. ``jax.jit(lambda v: hybrid_shuffle(...))``
    on a cold cache), and caching trace-scoped tracers here would leak them
    into every later caller."""
    with jax.ensure_compile_time_eval():
        return DevicePlanTables(
            jnp.asarray(plan.cross_send_pos),
            jnp.asarray(plan.cross_recv_pos),
            jnp.asarray(plan.local_pos),
            jnp.asarray(plan.mcast_comp_pos),
            jnp.asarray(plan.mcast_comp_rack),
            jnp.asarray(plan.mcast_known_pos),
            jnp.asarray(plan.mcast_known_rack),
            None if plan.cross_valid is None
            else jnp.asarray(plan.cross_valid))


def _combine(streams, multicast: str, combine_impl: str):
    """Encode r component streams (list of same-shape arrays) into one packet
    stream — the paper's f(.) (eq. (1), unit coefficients) or its GF(2)
    variant."""
    if combine_impl == "pallas":
        from ..kernels.coded_combine import ops as cc_ops
        if multicast == "coded_xor":
            return cc_ops.xor_encode(streams)
        return cc_ops.coded_encode(streams, jnp.ones(len(streams)))
    if multicast == "coded_xor":
        return functools.reduce(jnp.bitwise_xor, streams)
    return functools.reduce(jnp.add, [s.astype(jnp.float32) for s in streams]
                            ).astype(streams[0].dtype)


def _uncombine(f, known, multicast: str, combine_impl: str):
    """Recover the missing component of packet stream ``f`` from the r-1
    known components (receiver side information)."""
    if not known:
        return f
    if combine_impl == "pallas":
        from ..kernels.coded_combine import ops as cc_ops
        if multicast == "coded_xor":
            return cc_ops.xor_decode(f, known)
        return cc_ops.coded_decode(f, known, jnp.ones(len(known) + 1))
    if multicast == "coded_xor":
        return functools.reduce(jnp.bitwise_xor, known, f)
    acc = functools.reduce(jnp.add,
                           [k.astype(jnp.float32) for k in known])
    return (f.astype(jnp.float32) - acc).astype(f.dtype)


def shuffle_device_body(vals: jax.Array, plan: HybridShufflePlan,
                        tables: DevicePlanTables,
                        multicast: str = "unicast",
                        combine_impl: str = "xla",
                        patch: Optional[jax.Array] = None) -> jax.Array:
    """Per-device body of the two-stage hybrid shuffle, general r.

    Runs inside a shard_map over ('rack', 'server').  ``vals`` is THIS
    device's [n_loc, Q, d] mapped values (rows ordered as
    ``plan.local_subfiles[i, j]``); returns its [N, q_srv, d] reduce rows
    (order = :func:`reduce_ready_order`).  Shared by :func:`hybrid_shuffle`
    and the fused device-resident pipeline of :mod:`repro.mapreduce.engine`.

    ``multicast='coded'`` replaces raw stage-1 rows with the paper's coded
    multicast packets f(v_1..v_arity) (unit coefficients), decoded at
    receivers from replicated-map side information; ``'coded_xor'`` is the
    GF(2) variant (integer payloads, bit-exact).  The packet arity is the
    plan's ``mcast_arity`` (r for binomial, r - 1 for resolvable);
    single-component streams degenerate to unicast.  ``combine_impl``
    selects the encode/decode implementation: ``'xla'`` (jnp adds) or
    ``'pallas'`` (the fused single-HBM-pass kernels of
    :mod:`repro.kernels.coded_combine`, interpret-mode off TPU).

    ``patch`` is this device's [n_layer, q_rack, d] additive stage-1 table
    correction — the degraded-recovery path of :mod:`repro.core.degraded`
    injects re-mapped orphan rows through it (those rows receive nothing
    and their local fill is zero, so add == set).  ``None`` costs nothing.
    """
    if multicast not in MULTICAST_MODES:
        raise ValueError(f"multicast must be one of {MULTICAST_MODES}")
    if combine_impl not in COMBINE_IMPLS:
        raise ValueError(f"combine_impl must be one of {COMBINE_IMPLS}")
    p = plan.params
    q_rack, q_srv = p.Q // p.P, p.Q // p.K
    n_layer = p.subfiles_per_layer
    d = vals.shape[-1]
    n_send = plan.n_send
    arity = plan.mcast_arity
    coded = multicast != "unicast" and arity >= 2

    i = jax.lax.axis_index("rack")
    j = jax.lax.axis_index("server")
    my_local = tables.local_pos[i, j]                # [n_loc]
    key_starts = jnp.arange(p.P) * q_rack
    key_off = jnp.arange(q_rack)

    # ---- Stage 1: cross-rack all_to_all over 'rack' ------------------------
    table = jnp.zeros((n_layer, q_rack, d), vals.dtype)
    my_keys = jax.lax.dynamic_slice_in_dim(vals, i * q_rack, q_rack, 1)
    table = table.at[my_local].set(my_keys)          # locally mapped rows
    if n_send > 0:
        if coded:
            # encode: gather the arity components of every packet of every
            # destination stream — component c of packet m to rack z is a
            # locally mapped row restricted to rack mcast_comp_rack[...,c]'s
            # key block — then combine with f(.)
            comp_pos = tables.mcast_comp_pos[i]      # [P, n_send, arity]
            cols = (tables.mcast_comp_rack[i][..., None] * q_rack
                    + key_off)                       # [P, n_send, ar, q_rack]
            comps = vals[comp_pos[..., None], cols]  # [P, n_send, ar, qr, d]
            blocks = _combine([comps[:, :, c] for c in range(arity)],
                              multicast, combine_impl)
        else:
            my_send = tables.send_pos[i, j]          # [P, n_send]

            def build_block(z):
                rows = jnp.take(vals, my_send[z], axis=0)   # [n_send, Q, d]
                return jax.lax.dynamic_slice_in_dim(
                    rows, key_starts[z], q_rack, 1)         # [n_send, qr, d]
            blocks = jax.vmap(build_block)(jnp.arange(p.P))  # [P,n_send,qr,d]
        recvd = jax.lax.all_to_all(blocks, "rack", split_axis=0,
                                   concat_axis=0, tiled=True)
        if coded:
            # decode: subtract the arity-1 known components (rows this
            # device mapped itself — the replicated-map side information)
            recvd = recvd.reshape(p.P, n_send, q_rack, d)
            kcols = (tables.mcast_known_rack[i][..., None] * q_rack
                     + key_off)                      # [P, n_send, ar-1, qr]
            known = vals[tables.mcast_known_pos[i][..., None], kcols]
            recvd = _uncombine(recvd,
                               [known[:, :, c] for c in range(arity - 1)],
                               multicast, combine_impl)
        my_recv = tables.recv_pos[i, j]
        flat_dst = my_recv.reshape(-1)                   # [P*n_send]
        flat_src = recvd.reshape(p.P * n_send, q_rack, d)
        if tables.cross_valid is None:
            # binomial: every slot from a distinct source rack is real
            valid = (jnp.repeat(jnp.arange(p.P), n_send) != i)
        elif tables.cross_valid.ndim == 4:
            # degraded plans: per-LAYER validity (repair streams differ by
            # which servers of the layer died)
            valid = tables.cross_valid[i, j].reshape(-1)
        else:
            # families with padded streams (resolvable): per-slot mask
            valid = tables.cross_valid[i].reshape(-1)
        # the senders' shares are disjoint slices of each block, so target
        # rows are hit at most once => add == set
        table = table.at[flat_dst].add(
            jnp.where(valid[:, None, None], flat_src, 0))
    if patch is not None:
        table = table + patch

    # ---- Stage 2: intra-rack all_to_all over 'server' ----------------------
    per_srv = table.reshape(n_layer, p.Kr, q_srv, d).transpose(1, 0, 2, 3)
    gathered = jax.lax.all_to_all(per_srv, "server", split_axis=0,
                                  concat_axis=0, tiled=True)
    return gathered.reshape(p.Kr * n_layer, q_srv, d)


def hybrid_shuffle(values_local: jax.Array, plan: HybridShufflePlan,
                   mesh: Mesh, multicast: str = "unicast",
                   combine_impl: str = "xla") -> jax.Array:
    """Two-stage hybrid shuffle, general r.

    values_local: [K, n_loc, Q, d], axis 0 sharded over ('rack','server');
      row (i*Kr + j) = device (i, j)'s mapped subfile values, ordered as
      ``plan.local_subfiles[i, j]``.
    Returns [K, N, q_srv, d]: per device, values of ALL N subfiles for its own
      q_srv reduce keys, rows ordered as :func:`reduce_ready_order`.

    ``multicast`` / ``combine_impl`` select the stage-1 wire format and the
    f(.) implementation — see :func:`shuffle_device_body`.
    """
    tables = device_plan_tables(plan)

    def device_fn(vals):                             # [1, n_loc, Q, d]
        return shuffle_device_body(vals[0], plan, tables, multicast,
                                   combine_impl)[None]

    # off-TPU the kernels run in the Pallas HLO interpreter, which slices
    # blocks with grid indices that carry no varying-axes type, and
    # check_vma rejects that; the body is per-device, so the check adds
    # nothing to the pallas path.  On the chip the Mosaic lowering might
    # pass with a vma-annotated out_shape; that has not been tried, so the
    # check is off there too.
    fn = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(P(("rack", "server")),),
                       out_specs=P(("rack", "server")),
                       check_vma=combine_impl != "pallas")
    return fn(values_local)


def hybrid_shuffle_r2(values_local: jax.Array, plan: HybridShufflePlan,
                      mesh: Mesh) -> jax.Array:
    """Back-compat alias for :func:`hybrid_shuffle` (r = 2 plans and any
    other compiled plan run through the identical program)."""
    return hybrid_shuffle(values_local, plan, mesh)


def reduce_ready_order(plan: HybridShufflePlan) -> np.ndarray:
    """Global subfile id of each output row of :func:`hybrid_shuffle`,
    per device: [P, Kr, N] (layer-major, canonical layer-table order)."""
    p = plan.params
    flat = np.asarray(plan.layer_subfiles).reshape(p.P, p.N)
    return np.broadcast_to(flat[:, None, :], (p.P, p.Kr, p.N))


def reduce_output_keys(plan: HybridShufflePlan) -> np.ndarray:
    """Global key id of each reduce row produced by server s: [K, Q/K].

    Output assembly must place server s's row q at global key
    ``reduce_output_keys(plan)[s, q]`` — derived from the key partition
    explicitly rather than assuming the flat [K * Q/K] order IS key order
    (true only for the default contiguous partition)."""
    p = plan.params
    return np.asarray([list(p.keys_of_server(s)) for s in range(p.K)],
                      dtype=np.int64)


def pack_local_values(values: np.ndarray,
                      plan: HybridShufflePlan) -> np.ndarray:
    """Distribute dense V[N, Q, d] into the per-device layout expected by
    :func:`hybrid_shuffle`: [K, n_loc, Q, d]."""
    p = plan.params
    return values[plan.local_subfiles.reshape(p.K, -1)]


def plan_transfer_matrices(plan: HybridShufflePlan,
                           multicast: str = "coded") -> Dict[str, np.ndarray]:
    """Per-round transfer matrices of the EXECUTABLE hybrid shuffle.

    Returns the actual traffic the compiled plan moves (all layers summed),
    in <key, value> pairs:

      * ``cross_rack_matrix`` [P, P]: stage-1 pairs the root switch carries
        from rack i to rack z.  ``multicast='unicast'`` counts the wire
        format of a unicast realization (each destination stream a separate
        copy); ``'coded'`` / ``'coded_xor'`` count the paper metric — each
        coded packet serves ``mcast_arity`` destination racks and traverses
        the root ONCE, so 1/arity is attributed to each of its streams (row
        sums = per-sender root load, total = the family's closed-form cross
        cost: ``hybrid_cost(p).cross`` or
        ``hybrid_resolvable_cost(p).cross``).  Families with padded streams
        report the ACTUAL per-pair loads (padding carries no pairs), so the
        matrix is not uniform — resolvable same-class rack pairs exchange
        nothing.
      * ``intra_per_rack`` [P]: stage-2 pairs through each ToR switch
        (identical per rack by symmetry; total = the closed-form intra
        cost, the same expression for both families).

    Degraded plans (4-dim ``cross_valid`` — see :mod:`repro.core.degraded`)
    are handled too: their stage-1 routing is per-layer repair unicast, so
    the matrix is counted straight off the valid slots (the multicast gain
    is forfeited during recovery regardless of ``multicast``).

    The `repro.sim` network model consumes these loads, so simulated traffic
    is the executable schedule — not a formula (their equality with the
    closed forms is nevertheless asserted in tests).
    """
    if multicast not in MULTICAST_MODES:
        raise ValueError(f"multicast must be one of {MULTICAST_MODES}")
    p = plan.params
    q_rack, q_srv = p.Q // p.P, p.Q // p.K
    intra_rack = float(p.Kr * (p.Kr - 1) * p.subfiles_per_layer * q_srv)
    cv = plan.cross_valid
    if cv is not None and getattr(cv, "ndim", 0) == 4:
        # valid slots summed over layers and slot axis: [recv i, src z]
        counts = cv.sum(axis=(1, 3)) if cv.size else np.zeros((p.P, p.P))
        return {"cross_rack_matrix": counts.T.astype(float) * q_rack,
                "intra_per_rack": np.full((p.P,), intra_rack)}
    arity = plan.mcast_arity
    gain = arity if (multicast != "unicast" and arity >= 2) else 1
    if plan.family == "resolvable":
        from .resolvable import shared_group_counts
        sh = p.M_res // (p.r - 1)
        cross = (shared_group_counts(p).astype(float)
                 * sh * p.Kr * q_rack / gain)
    else:
        per_stream = float(p.Kr * plan.n_send * q_rack) / gain
        cross = np.full((p.P, p.P), per_stream)
        np.fill_diagonal(cross, 0.0)
    return {"cross_rack_matrix": cross,
            "intra_per_rack": np.full((p.P,), intra_rack)}


def plan_shuffle_reference(values: np.ndarray, p: SchemeParams,
                           family: str = "binomial") -> np.ndarray:
    """Oracle: [K, N, q_srv, d] that a correct shuffle must deliver, in the
    row order of :func:`reduce_ready_order`."""
    plan = compile_hybrid_plan(p, family=family)
    order = reduce_ready_order(plan)
    q_srv = p.Q // p.K
    out = np.zeros((p.K, p.N, q_srv, values.shape[-1]), values.dtype)
    for i in range(p.P):
        for j in range(p.Kr):
            s = p.server_id(i, j)
            keys = list(p.keys_of_server(s))
            out[s] = values[order[i, j]][:, keys, :]
    return out


def simulate_plan_shuffle(values: np.ndarray, plan: HybridShufflePlan,
                          multicast: str = "unicast", *,
                          failed: Sequence[int] = (),
                          patch: Optional[np.ndarray] = None) -> np.ndarray:
    """Re-execute the exact data movement of :func:`hybrid_shuffle` with
    NumPy indexing: stage-1 table fill (local rows + per-source-rack
    received blocks), then the stage-2 intra-rack key split.  Independent of
    jax and of device count, so it validates the index tables of ANY
    registered plan family in-process — the decodability oracle of the
    tests and of ``benchmarks/scale_bench.py``.

    ``multicast='coded'`` re-executes the coded wire format instead: each
    stage-1 packet is the SUM of its ``mcast_arity`` components (built from
    the sender's ``mcast_comp_*`` tables) and the receiver decodes by
    subtracting its arity-1 locally-known components (``mcast_known_*``) —
    NumPy end to end, so it proves decodability of the multicast tables
    themselves.  Plans with padded streams contribute only their
    ``cross_valid`` slots, exactly like the device body's receive mask.

    ``failed`` (flat server ids) zeroes those devices' in-memory map outputs
    before the shuffle — the crash model of :mod:`repro.core.degraded` —
    and ``patch`` adds a [K, n_layer, q_rack, d] per-device stage-1
    correction (re-mapped orphan rows) after the table fill, mirroring the
    ``patch`` argument of :func:`shuffle_device_body`.  Together they make
    this oracle re-execute a DEGRADED plan exactly as the 8-device driver
    would, still independent of jax."""
    p = plan.params
    q_rack, q_srv = p.Q // p.P, p.Q // p.K
    n_layer = p.subfiles_per_layer
    d = values.shape[-1]
    local = pack_local_values(values, plan).reshape(
        p.P, p.Kr, -1, p.Q, d)                      # [P, Kr, n_loc, Q, d]
    if failed:
        local = local.copy()
        for s in failed:
            local[int(s) // p.Kr, int(s) % p.Kr] = 0
    arity = plan.mcast_arity
    coded = multicast == "coded" and arity >= 2

    # ---- Stage 1: per-device layer table over its rack's q_rack keys ------
    table = np.zeros((p.P, p.Kr, n_layer, q_rack, d), values.dtype)
    for i in range(p.P):
        keys_i = np.arange(i * q_rack, (i + 1) * q_rack)
        for j in range(p.Kr):
            table[i, j, plan.local_pos[i, j]] = local[i, j][:, keys_i]
            if plan.n_send:
                for z in range(p.P):
                    if z == i:
                        continue
                    cv = plan.cross_valid
                    valid = (slice(None) if cv is None
                             else cv[i, j, z] if cv.ndim == 4
                             else cv[i, z])
                    dst = plan.cross_recv_pos[i, j, z][valid]
                    if not coded:
                        # what z sends to i: its share rows, i's rack keys
                        sent = local[z, j][plan.cross_send_pos[z, j, i]][
                            :, keys_i]
                        table[i, j, dst] = sent[valid]
                        continue
                    # sender z encodes packets for destination i
                    cpos = plan.mcast_comp_pos[z, i]     # [n_send, arity]
                    ckey = (plan.mcast_comp_rack[z, i][..., None] * q_rack
                            + np.arange(q_rack))         # [n_send, ar, qr]
                    f = local[z, j][cpos[..., None],
                                    ckey].sum(axis=1)    # [n_send, qr, d]
                    # receiver i decodes with its side information
                    kpos = plan.mcast_known_pos[i, z]    # [n_send, arity-1]
                    kkey = (plan.mcast_known_rack[i, z][..., None] * q_rack
                            + np.arange(q_rack))
                    side = local[i, j][kpos[..., None], kkey].sum(axis=1)
                    table[i, j, dst] = (f - side)[valid]
    if patch is not None:
        table = table + np.asarray(patch).reshape(
            p.P, p.Kr, n_layer, q_rack, d)

    # ---- Stage 2: intra-rack all_to_all == per-server key split -----------
    out = np.zeros((p.K, p.Kr * n_layer, q_srv, d), values.dtype)
    for i in range(p.P):
        for j in range(p.Kr):
            s = p.server_id(i, j)
            # device (i, j) collects key-chunk j of every layer jp's table
            out[s] = table[i, :, :, j * q_srv:(j + 1) * q_srv, :].reshape(
                p.Kr * n_layer, q_srv, d)
    return out


# Register the resolvable-design family (import side effect; kept at module
# bottom — resolvable.py needs only plan_registry/params/assignment, so no
# cycle, but its docstrings reference this module's executable paths).
from . import resolvable as _resolvable_family  # noqa: E402,F401

__all__ = [
    "HybridShufflePlan", "HybridShufflePlanR2", "register_plan_compiler",
    "get_plan_compiler", "plan_families", "compile_hybrid_plan",
    "compile_hybrid_plan_r2", "configure_plan_cache", "plan_cache_info",
    "plan_cache_clear", "PlanCacheInfo", "FamilyCacheInfo",
    "PLAN_CACHE_MAXSIZE_ENV", "MULTICAST_MODES", "COMBINE_IMPLS",
    "DevicePlanTables", "device_plan_tables", "shuffle_device_body",
    "hybrid_shuffle", "hybrid_shuffle_r2", "reduce_ready_order",
    "reduce_output_keys", "pack_local_values", "plan_transfer_matrices",
    "plan_shuffle_reference", "simulate_plan_shuffle",
]

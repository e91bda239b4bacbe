"""Rack-level byte accounting as a first-class instrument.

The paper's central quantity is the split of shuffle traffic into
intra-rack and cross-rack <key, value> pairs.  This module derives the
per-(src_rack, dst_rack) transfer matrix of the ACTUAL compiled plan
(:func:`repro.core.coded_collectives.plan_transfer_matrices`, which also
handles degraded plans), scales it to value-units (pairs x payload width
``d`` — the unit the fluid network and cost model share), records it into
the metrics registry, and asserts the measured schedule reconciles with the
``CommCost`` closed forms (Props 1-2 / Thm III.1 / the resolvable family's
closed form).

Three counting conventions appear; keep them straight:

  * **paper metric** (``multicast='coded'``): a coded multicast packet
    traverses the root ONCE — this is what ``CommCost`` closed forms count
    and what ``intra_rack_bytes`` / ``cross_rack_bytes`` on ``JobResult``
    and ``JobStats`` report, so engine and sim agree by construction;
  * **wire format** (``multicast='unicast'``): each destination stream is a
    separate copy — what a unicast realization actually moves;
  * **degraded**: recovery runs unicast (the multicast gain is forfeited),
    so its matrix comes straight from the degraded plan's 4-dim
    ``cross_valid`` routing, plus one per-rack redistribution of each
    re-mapped orphan subfile (``n_remap * Q`` pairs — the same term
    :func:`repro.core.degraded.degraded_stage_traffic` prices).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from . import metrics as _metrics


class ByteReconciliationError(AssertionError):
    """Measured schedule bytes do not match the closed-form ``CommCost`` —
    either the plan compiler and the cost theorems disagree (a real bug) or
    the caller mixed counting conventions (see module docstring)."""


@dataclasses.dataclass(frozen=True)
class RackBytes:
    """Value-unit transfer accounting of one shuffle schedule.

    ``cross_matrix[src, dst]`` is stage-1 root-switch value-units from rack
    src to rack dst; ``intra_per_rack[rack]`` stage-2 units through that
    rack's ToR.  ``d`` is the payload width the pair counts were scaled by.
    """
    cross_matrix: np.ndarray          # [P, P]
    intra_per_rack: np.ndarray        # [P]
    d: int = 1

    @property
    def cross_total(self) -> float:
        return float(self.cross_matrix.sum())

    @property
    def intra_total(self) -> float:
        return float(self.intra_per_rack.sum())

    def to_dict(self) -> Dict[str, object]:
        return {"cross_matrix": self.cross_matrix.tolist(),
                "intra_per_rack": self.intra_per_rack.tolist(),
                "cross_total": self.cross_total,
                "intra_total": self.intra_total, "d": int(self.d)}


def plan_rack_bytes(plan, multicast: str = "coded", d: int = 1) -> RackBytes:
    """Rack-level value-units of a compiled plan (failure-free OR degraded —
    ``plan_transfer_matrices`` dispatches on the ``cross_valid`` schema).

    ``multicast='coded'`` counts the paper metric; ``'unicast'`` the wire
    format of a unicast realization.  Accepts a ``HybridShufflePlan`` or a
    :class:`repro.core.degraded.DegradedPlan` (its re-routed plan is used).
    """
    from ..core.coded_collectives import plan_transfer_matrices
    inner = getattr(plan, "plan", plan)       # DegradedPlan -> its tables
    tm = plan_transfer_matrices(inner, multicast=multicast)
    return RackBytes(np.asarray(tm["cross_rack_matrix"], dtype=float) * d,
                     np.asarray(tm["intra_per_rack"], dtype=float) * d, d)


def degraded_rack_bytes(dplan, d: int = 1) -> RackBytes:
    """Value-units of a degraded recovery schedule: the unicast degraded
    routing plus the orphan-redistribution term (each re-mapped subfile's
    [Q, d] values reach every rack once — priced identically by the sim's
    crash recovery).  The redistribution has no single (src, dst) pair, so
    it is spread uniformly over off-diagonal entries to keep the matrix
    total exact."""
    rb = plan_rack_bytes(dplan, multicast="unicast", d=d)
    n_remap = int(dplan.orphan_subfiles.size)
    if n_remap == 0:
        return rb
    p = dplan.params
    extra = float(n_remap * p.Q * d)
    cross = rb.cross_matrix.copy()
    off = p.P * (p.P - 1)
    if off > 0:
        add = np.full((p.P, p.P), extra / off)
        np.fill_diagonal(add, 0.0)
        cross = cross + add
    return RackBytes(cross, rb.intra_per_rack, d)


def closed_form_bytes(p, scheme: str, d: int = 1,
                      check: bool = False) -> Dict[str, float]:
    """``CommCost`` closed form of ``scheme`` scaled to value-units:
    {'intra', 'cross', 'total'}.  ``check=False`` (default) evaluates the
    formula even on divisibility-violating Table I rows, as the paper did.
    """
    from ..core.costs import (coded_cost, hybrid_cost,
                              hybrid_resolvable_cost, uncoded_cost)
    fn = {"uncoded": uncoded_cost, "coded": coded_cost,
          "hybrid": hybrid_cost,
          "hybrid_resolvable": hybrid_resolvable_cost}[scheme]
    c = fn(p, check=check)
    return {"intra": c.intra * d, "cross": c.cross * d,
            "total": c.total * d}


def reconcile(measured_intra: float, measured_cross: float, p, scheme: str,
              d: int = 1, rtol: float = 1e-9, atol: float = 1e-6,
              check: bool = False) -> Dict[str, float]:
    """Assert measured schedule bytes equal the closed form; returns the
    comparison report.  Raises :class:`ByteReconciliationError` with both
    sides on mismatch — the invariant every instrumented job run re-checks
    (the simulated/executed traffic IS the schedule, not a formula, so this
    equality is a theorem being re-proven per job)."""
    cf = closed_form_bytes(p, scheme, d=d, check=check)
    report = {"measured_intra": float(measured_intra),
              "measured_cross": float(measured_cross),
              "closed_intra": cf["intra"], "closed_cross": cf["cross"]}
    for tier in ("intra", "cross"):
        m, c = report[f"measured_{tier}"], report[f"closed_{tier}"]
        if abs(m - c) > atol + rtol * max(abs(m), abs(c)):
            raise ByteReconciliationError(
                f"{tier}-rack bytes do not reconcile for scheme={scheme!r} "
                f"{p}: measured {m!r} != closed-form {c!r}")
    return report


def record_rack_bytes(rb: RackBytes, scheme: str, family: str = "",
                      layer: str = "engine",
                      reg: Optional[_metrics.MetricsRegistry] = None
                      ) -> RackBytes:
    """Record a schedule's rack-level bytes into the metrics registry:

      * ``shuffle_bytes_total{tier=intra|cross, scheme, family, layer}`` —
        the paper's headline split, cumulative across jobs;
      * ``rack_pair_bytes_total{src, dst, layer}`` — the [P, P] matrix
        (bounded cardinality: P^2 label sets for the cluster's fixed P).

    Returns ``rb`` unchanged so call sites can thread it through."""
    reg = reg if reg is not None else _metrics.registry()
    tot = reg.counter("shuffle_bytes_total",
                      "shuffle value-units moved, by tier")
    tot.inc(rb.intra_total, tier="intra", scheme=scheme, family=family,
            layer=layer)
    tot.inc(rb.cross_total, tier="cross", scheme=scheme, family=family,
            layer=layer)
    pair = reg.counter("rack_pair_bytes_total",
                       "cross-rack value-units per (src, dst) rack pair")
    P = rb.cross_matrix.shape[0]
    for src in range(P):
        for dst in range(P):
            v = float(rb.cross_matrix[src, dst])
            if v > 0:
                pair.inc(v, src=src, dst=dst, layer=layer)
    return rb


def record_shuffle_slots(job: str, records: int, padding: int,
                         reg: Optional[_metrics.MetricsRegistry] = None
                         ) -> None:
    """Record one job's map-output record slots in
    ``shuffle_record_slots_total{job, kind=record|padding}``: the slots
    that carry a record and those that pad a fixed-capacity block (they
    cross the wire all the same)."""
    reg = reg if reg is not None else _metrics.registry()
    slots = reg.counter("shuffle_record_slots_total",
                        "map-output record slots shipped, records vs "
                        "padding")
    slots.inc(records, job=job, kind="record")
    slots.inc(padding, job=job, kind="padding")


__all__ = [
    "RackBytes", "ByteReconciliationError", "plan_rack_bytes",
    "degraded_rack_bytes", "closed_form_bytes", "reconcile",
    "record_rack_bytes", "record_shuffle_slots",
]

"""Shared benchmark CLI plumbing: arg parsing, seeding, JSON report emit.

Every ``BENCH_*.json`` artifact carries the same envelope so downstream
tooling (CI artifact diffing, the simulator's calibration loader) can parse
any of them: ``schema_version``, ``bench``, ``smoke``, ``seed``, plus the
bench-specific payload.  Bump :data:`SCHEMA_VERSION` on envelope changes.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, Optional

import numpy as np

from repro.compile_cache import enable_compile_cache

SCHEMA_VERSION = 1

# every bench imports this module before its first compile
enable_compile_cache()


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_out_path(name: str) -> str:
    return os.path.join(repo_root(), name)


def make_parser(description: str, out_name: str, default_iters: int = 8,
                add_seed: bool = True) -> argparse.ArgumentParser:
    """Standard benchmark CLI: ``--smoke`` (small config, few iters, CI),
    ``--iters``, ``--out``, and (unless the bench has no rng —
    ``add_seed=False``) ``--seed``."""
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--smoke", action="store_true",
                    help="one small config, few iters (CI)")
    ap.add_argument("--iters", type=int, default=default_iters)
    if add_seed:
        ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=default_out_path(out_name))
    return ap


def seeded_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(seed)


def emit_report(report: Dict, bench: str, out_path: str,
                smoke: bool = False, seed: Optional[int] = None) -> Dict:
    """Wrap ``report`` in the common envelope, write it to ``out_path``,
    and append the bench's headline scalars to the trajectory ledger
    (``BENCH_history.jsonl`` next to ``out_path`` — see
    :mod:`benchmarks.history`)."""
    envelope = {
        "schema_version": SCHEMA_VERSION,
        "bench": bench,
        "smoke": smoke,
        **({} if seed is None else {"seed": seed}),
        **report,
    }
    with open(out_path, "w") as f:
        json.dump(envelope, f, indent=2)
    print(f"wrote {out_path}")
    try:
        from .history import append_entry
    except ImportError:                   # run as a script, not a package
        from history import append_entry
    append_entry(envelope, out_path)
    return envelope

"""Executable-shuffle benchmark: runs the REAL distributed two-stage hybrid
shuffle (shard_map all_to_all over a ('rack','server') host-device mesh)
against the dense oracle, times the coded-combine kernel paths, sweeps the
map-replication factor r (the paper's computation/communication tradeoff
curve, emitting per-r cross/intra traffic), and times general-r plan
compilation (cold vs LRU-cached).

Byte accounting comes from the schedule enumerator (== closed forms,
asserted); wall-times here are CPU host-device times (structural, not TPU
perf — the TPU story is the dry-run roofline)."""
from __future__ import annotations

import os
import subprocess
import sys
import time

try:
    from ._common import emit_report, make_parser
except ImportError:                       # run as a script, not a package
    from _common import emit_report, make_parser

# r sweep config: P=4 racks x Kr=2; N=2016 satisfies C(4,r) | NP/K and
# r | M for r in {1, 2, 3, 4} — one config, the whole tradeoff curve.
SWEEP = dict(K=8, P=4, Q=16, N=2016)
PAYLOAD_BYTES = 4                    # fp32 <key, value> payload unit


def _kernel_times(iters: int = 10, smoke: bool = False) -> list:
    import jax
    import jax.numpy as jnp
    from repro.kernels.coded_combine import ops
    rows = []
    key = jax.random.PRNGKey(0)
    shapes = [(2, 4096, 256), (3, 4096, 256), (4, 16384, 512)]
    for r, T, d in shapes[:1] if smoke else shapes:
        streams = [jax.random.normal(jax.random.fold_in(key, i), (T, d))
                   for i in range(r)]
        coeffs = jnp.arange(1.0, r + 1.0)
        f = ops.coded_encode(streams, coeffs)          # compile
        f.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            f = ops.coded_encode(streams, coeffs)
        f.block_until_ready()
        enc_us = (time.perf_counter() - t0) / iters * 1e6
        dec = ops.coded_decode(f, streams[1:], coeffs)
        dec.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            dec = ops.coded_decode(f, streams[1:], coeffs)
        dec.block_until_ready()
        dec_us = (time.perf_counter() - t0) / iters * 1e6
        gb = r * T * d * 4 / 1e9
        rows.append((f"coded_encode_r{r}_{T}x{d}", enc_us,
                     f"{gb / (enc_us / 1e6):.2f}GB/s-interp"))
        rows.append((f"coded_decode_r{r}_{T}x{d}", dec_us,
                     f"{gb / (dec_us / 1e6):.2f}GB/s-interp"))
    return rows


def _r_sweep() -> list:
    """Per-r shuffle traffic (closed forms == enumerated schedule, asserted
    in tests) and general-r plan-compilation time, cold vs cached."""
    from repro.core.coded_collectives import compile_hybrid_plan
    from repro.core.costs import hybrid_cost
    from repro.core.params import SchemeParams

    rows = []
    for r in (1, 2, 3, 4):
        p = SchemeParams(r=r, **SWEEP)
        compile_hybrid_plan.cache_clear()
        t0 = time.perf_counter()
        compile_hybrid_plan(p)
        cold_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        compile_hybrid_plan(p)
        warm_us = (time.perf_counter() - t0) * 1e6
        c = hybrid_cost(p)
        rows.append((f"compile_plan_r{r}_N{p.N}", cold_us,
                     f"cached={warm_us:.0f}us "
                     f"cross={c.cross * PAYLOAD_BYTES:.0f}B "
                     f"intra={c.intra * PAYLOAD_BYTES:.0f}B"))
    return rows


def run(verbose: bool = True, iters: int = 10, smoke: bool = False) -> list:
    """``smoke`` keeps one kernel shape and skips the ~5-min 8-device
    subprocess — the reduced CI profile."""
    rows = _kernel_times(iters=iters, smoke=smoke) + _r_sweep()
    if not smoke:
        # distributed shuffle in a subprocess: a CPU simulation on 8 host
        # devices by design, kept off any chip this process holds
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable,
             os.path.join(root, "tests", "multidevice", "driver_shuffle.py")],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "PYTHONPATH": os.path.join(root, "src"),
                 "JAX_PLATFORMS": "cpu"})
        ok = proc.returncode == 0 and "ALL MULTIDEVICE" in proc.stdout
        rows.append(("distributed_hybrid_shuffle_8dev_r123",
                     (time.perf_counter() - t0) * 1e6,
                     "bit-exact" if ok else "FAILED"))
    if verbose:
        for name, us, derived in rows:
            print(f"{name:40s} {us:12.1f} us  {derived}")
    return rows


def main() -> None:
    # CSV entry point of the `python -m benchmarks.run` aggregator
    rows = run(verbose=False)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


def cli() -> None:
    # no --seed / envelope seed: this bench times fixed workloads, nothing
    # here is rng-driven
    args = make_parser(__doc__, "BENCH_shuffle.json",
                       add_seed=False).parse_args()
    rows = run(verbose=True, iters=2 if args.smoke else args.iters,
               smoke=args.smoke)
    emit_report(
        {"results": [{"name": n, "us": us, "derived": derived}
                     for n, us, derived in rows]},
        "shuffle", args.out, smoke=args.smoke)


if __name__ == "__main__":
    cli()

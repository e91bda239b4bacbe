#!/usr/bin/env python3
"""Smoke run of the MapReduce engine on TPU: proves the main path starts,
compiles and gives bit-exact answers on the chip.

    python chip_smoke.py             # one chip: engine at 2 GiB + kernels
    python chip_smoke.py --chips 4   # four chips: the two-stage shuffle only

One chip: ``run_job_distributed`` on a (1, 1) ('rack', 'server') mesh,
N=512 subfiles x 2^20 int32 tokens (2 GiB) -> Q=1024 keys x d=128, twice
(cold, then warm), bit-exact against ``run_job``; then the coded_combine
Pallas kernels at shuffle widths, bit-exact against the XLA combine, each
checked to hold a compiled Mosaic kernel (``tpu_custom_call``).

Four chips: the fused engine on meshes (2, 2) and (4, 1) at every r the
mesh admits, under unicast and coded multicast with the XLA and Pallas
combines (plus one GF(2) coded_xor case), each bit-exact against
``run_job`` on one device.

Every phase runs in this one process, which holds the chip(s).  Without a
TPU the script exits non-zero before doing anything.  The last line of
stdout is the JSON verdict ``{"ok": true, "device": {...}}``; any failure
raises instead.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _compile_seconds():
    """Running total of backend compile time (JAX's own monitoring
    event), so a cold call's compile share can be reported."""
    import jax
    total = [0.0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration
    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def _assert_same(got, want, what: str) -> None:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        raise AssertionError(f"{what}: {got.shape}/{got.dtype} != "
                             f"{want.shape}/{want.dtype}")
    bad = int(np.count_nonzero(got != want))
    if bad:
        raise AssertionError(f"{what}: {bad} of {got.size} elements differ")


def _assert_mosaic(fn, args, what: str) -> None:
    """The compiled call holds a Mosaic kernel, not the interpreter."""
    if "tpu_custom_call" not in fn.lower(*args).compile().as_text():
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled "
                             f"program")


def _peak_bytes() -> int:
    import jax
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


def _tokens(seed: int, n: int, t: int):
    import numpy as np
    return np.random.default_rng(seed).integers(
        0, 1 << 31, size=(n, t), dtype=np.int32)


def engine_phase(seed: int, N: int = 512, tokens: int = 1 << 20,
                 Q: int = 1024, d: int = 128) -> None:
    """One chip: K=P=1, r=1 — map, (size-1) shuffle, reduce, assembly."""
    import jax
    import jax.numpy as jnp
    from repro.core.params import SchemeParams
    from repro.mapreduce.engine import run_job, run_job_distributed
    from repro.mapreduce.jobs import wide_histogram_job

    job = wide_histogram_job(d)
    params = SchemeParams(K=1, P=1, Q=Q, N=N, r=1)
    subfiles, gen_s = _timed(lambda: _tokens(seed, N, tokens))
    print(f"engine: input {subfiles.nbytes / 2**30:.3f} GiB "
          f"({N} subfiles x {tokens} int32), generated in {gen_s:.3f} s")
    mesh = jax.make_mesh((1, 1), ("rack", "server"))
    compile_s = _compile_seconds()

    def call():
        res = run_job_distributed(job, subfiles, params, mesh)
        return jax.block_until_ready(res.outputs)
    c0 = compile_s()
    out, cold_s = _timed(call)
    print(f"engine: cold call {cold_s:.3f} s "
          f"(backend compile {compile_s() - c0:.3f} s)")
    out2, warm_s = _timed(call)
    print(f"engine: warm call {warm_s:.3f} s")
    _assert_same(out2, out, "engine warm vs cold")
    print(f"engine: peak_bytes_in_use {_peak_bytes()} after the two calls")
    ref, ref_s = _timed(lambda: jax.block_until_ready(
        run_job(job, jnp.asarray(subfiles), params).outputs))
    _assert_same(out, ref, "engine vs run_job")
    print(f"engine: outputs {tuple(out.shape)} {out.dtype} bit-exact vs "
          f"run_job ({ref_s:.3f} s)")


def kernel_phase(seed: int, T: int = 4096) -> None:
    """coded_combine kernels vs the shuffle's XLA combine, bit-exact."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.coded_collectives import _combine, _uncombine
    from repro.kernels.coded_combine import ops

    rng = np.random.default_rng(seed + 1)
    for r in (2, 3):
        for d in (512, 2048):
            # integer-valued payloads: every partial sum is exact in f32
            xs = [jnp.asarray(rng.integers(-2**20, 2**20, size=(T, d)),
                              jnp.float32) for _ in range(r)]
            ones = jnp.ones(r)
            xi = [jnp.asarray(rng.integers(0, 2**31, size=(T, d),
                                           dtype=np.int32)) for _ in range(r)]
            f = _combine(xs, "coded", "xla")
            fi = _combine(xi, "coded_xor", "xla")
            cases = [
                ("coded_encode", ops.coded_encode, (xs, ones), f),
                ("coded_decode", ops.coded_decode, (f, xs[1:], ones),
                 _uncombine(f, xs[1:], "coded", "xla")),
                ("xor_encode", ops.xor_encode, (xi,), fi),
                ("xor_decode", ops.xor_decode, (fi, xi[1:]),
                 _uncombine(fi, xi[1:], "coded_xor", "xla")),
            ]
            for name, fn, args, want in cases:
                _assert_mosaic(fn, args, f"{name} r={r} d={d}")
                got = jax.block_until_ready(fn(*args))
                _assert_same(got, want, f"{name} r={r} d={d}")
                print(f"kernel: {name} r={r} T={T} d={d}: tpu_custom_call, "
                      f"bit-exact vs xla combine")


def four_chip_phase(seed: int, N: int = 96, tokens: int = 1 << 18,
                    Q: int = 1024, d: int = 2048) -> None:
    """The fused two-stage shuffle across chips, every case vs run_job."""
    import jax
    import jax.numpy as jnp
    from repro.core.params import SchemeParams
    from repro.mapreduce.engine import run_job, run_job_distributed
    from repro.mapreduce.jobs import wide_histogram_job

    subfiles = _tokens(seed, N, tokens)
    jobs = {"f32": wide_histogram_job(d),
            "i32": wide_histogram_job(d, jnp.int32)}
    ref = {k: jax.block_until_ready(run_job(
        job, jnp.asarray(subfiles), SchemeParams(K=1, P=1, Q=Q, N=N, r=1)
    ).outputs) for k, job in jobs.items()}
    print(f"four-chip: input {N} subfiles x {tokens} int32, Q={Q}, d={d}; "
          f"run_job reference on one device")
    compile_s = _compile_seconds()
    cases = []
    for (P_, Kr), rs in (((2, 2), (1, 2)), ((4, 1), (1, 2, 3))):
        for r in rs:
            for mc, impl in (("unicast", "xla"), ("coded", "xla"),
                             ("coded", "pallas")):
                cases.append(((P_, Kr), r, mc, impl, "f32"))
    cases.append(((4, 1), 3, "coded_xor", "pallas", "i32"))
    for (P_, Kr), r, mc, impl, kind in cases:
        mesh = jax.make_mesh((P_, Kr), ("rack", "server"))
        params = SchemeParams(K=P_ * Kr, P=P_, Q=Q, N=N, r=r)
        c0 = compile_s()
        res, s = _timed(lambda: run_job_distributed(
            jobs[kind], subfiles, params, mesh, multicast=mc,
            combine_impl=impl))
        out = jax.block_until_ready(res.outputs)
        _assert_same(out, ref[kind],
                     f"mesh {(P_, Kr)} r={r} {mc}/{impl} {kind}")
        print(f"four-chip: mesh ({P_},{Kr}) r={r} {mc}/{impl} {kind}: "
              f"bit-exact vs run_job, cold call {s:.3f} s (backend compile "
              f"{compile_s() - c0:.3f} s)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip shuffle phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} device(s)")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind} x {len(devices)}")

    if args.chips == 1:
        engine_phase(args.seed)
        kernel_phase(args.seed)
    else:
        four_chip_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

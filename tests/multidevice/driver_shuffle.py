"""Subprocess driver: validates the DISTRIBUTED shard_map shuffle and the
coded gradient collectives on a multi-device host mesh.

Run as:  python tests/multidevice/driver_shuffle.py
(spawned by tests/test_multidevice.py so the main pytest process keeps its
single-device view).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import numpy as np                                             # noqa: E402
import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
from jax.sharding import PartitionSpec as P                    # noqa: E402

from repro.core.params import SchemeParams                     # noqa: E402
from repro.core.coded_collectives import (                     # noqa: E402
    compile_hybrid_plan, compile_hybrid_plan_r2, hybrid_shuffle,
    hybrid_shuffle_r2, pack_local_values, plan_shuffle_reference)
from repro.core.gradient_sync import (                         # noqa: E402
    chunk_index_table, coded_reduce_scatter_r2, hierarchical_allreduce,
    uncoded_reduce_scatter)
from repro.distributed.meshes import make_mesh                 # noqa: E402
from repro.mapreduce.engine import run_job, run_job_distributed  # noqa: E402
from repro.mapreduce.jobs import histogram_job, groupby_mean_job  # noqa: E402


def test_distributed_hybrid_shuffle():
    # P=4 racks x Kr=2 servers = 8 devices; N=48 satisfies C(4,r) | NP/K
    # and r | M for every r in {1, 2, 3} — the paper's tradeoff sweep
    mesh = make_mesh((4, 2), ("rack", "server"))
    for r in (1, 2, 3):
        p = SchemeParams(K=8, P=4, Q=16, N=48, r=r)
        plan = compile_hybrid_plan(p)
        rng = np.random.default_rng(r)
        V = rng.integers(-100, 100, size=(p.N, p.Q, 3)).astype(np.float32)
        local = pack_local_values(V, plan)
        out = np.asarray(hybrid_shuffle(jnp.asarray(local), plan, mesh))
        ref = plan_shuffle_reference(V, p)
        np.testing.assert_array_equal(out, ref)
        print(f"distributed hybrid shuffle r={r}: OK (bit-exact vs oracle)")

    # r=2 back-compat aliases: identical program, identical output
    p = SchemeParams(K=8, P=4, Q=16, N=48, r=2)
    plan = compile_hybrid_plan_r2(p)
    rng = np.random.default_rng(2)
    V = rng.integers(-100, 100, size=(p.N, p.Q, 3)).astype(np.float32)
    out = np.asarray(hybrid_shuffle_r2(
        jnp.asarray(pack_local_values(V, plan)), plan, mesh))
    np.testing.assert_array_equal(out, plan_shuffle_reference(V, p))
    print("hybrid_shuffle_r2 alias: OK (unchanged behavior)")


def test_coded_multicast_shuffle():
    """Stage-1 coded multicast wire format (the paper's f(.) with receiver-
    side decode from replicated-map side information): bit-exact vs the
    oracle for r in {2, 3}, under both the XLA and the Pallas coded_combine
    implementations, in sum and GF(2)/XOR codecs."""
    mesh = make_mesh((4, 2), ("rack", "server"))
    for r in (2, 3):
        p = SchemeParams(K=8, P=4, Q=16, N=48, r=r)
        plan = compile_hybrid_plan(p)
        rng = np.random.default_rng(10 + r)
        V = rng.integers(-100, 100, size=(p.N, p.Q, 3)).astype(np.float32)
        local = jnp.asarray(pack_local_values(V, plan))
        ref = plan_shuffle_reference(V, p)
        for impl in ("xla", "pallas"):
            out = np.asarray(hybrid_shuffle(local, plan, mesh,
                                            multicast="coded",
                                            combine_impl=impl))
            np.testing.assert_array_equal(out, ref)
        Vi = rng.integers(0, 2 ** 30, size=(p.N, p.Q, 3)).astype(np.int32)
        li = jnp.asarray(pack_local_values(Vi, plan))
        refi = plan_shuffle_reference(Vi, p)
        for impl in ("xla", "pallas"):
            out = np.asarray(hybrid_shuffle(li, plan, mesh,
                                            multicast="coded_xor",
                                            combine_impl=impl))
            np.testing.assert_array_equal(out, refi)
        print(f"coded multicast shuffle r={r}: OK "
              "(sum+xor, xla+pallas, bit-exact)")


def test_fused_pipeline_parity():
    """The single jitted device-resident map->pack->shuffle->reduce program
    is bit-exact vs the run_job oracle for r in {1, 2, 3}, including under
    coded multicast and the Pallas combine kernels."""
    mesh = make_mesh((4, 2), ("rack", "server"))
    p = SchemeParams(K=8, P=4, Q=16, N=48, r=2)
    rng = np.random.default_rng(20)
    job = histogram_job()
    subs = np.asarray(rng.integers(0, 1 << 16, size=(p.N, 256)), np.int32)
    ref = run_job(job, jnp.asarray(subs), p, "hybrid")
    for r in (1, 2, 3):
        got = run_job_distributed(job, subs, p, mesh, r=r, fused=True)
        np.testing.assert_array_equal(np.asarray(got.outputs),
                                      np.asarray(ref.outputs))
        print(f"fused pipeline r={r}: OK (bit-exact vs run_job)")
    got = run_job_distributed(job, subs, p, mesh, fused=True,
                              multicast="coded", combine_impl="pallas")
    np.testing.assert_array_equal(np.asarray(got.outputs),
                                  np.asarray(ref.outputs))
    print("fused pipeline coded/pallas: OK (bit-exact)")

    job2 = groupby_mean_job()
    rows = jnp.asarray(rng.normal(size=(p.N, 128, 2)) * 100, jnp.float32)
    ref2 = run_job(job2, rows, p, "hybrid")
    got2 = run_job_distributed(job2, np.asarray(rows), p, mesh, fused=True)
    np.testing.assert_allclose(np.asarray(got2.outputs),
                               np.asarray(ref2.outputs), rtol=1e-5)
    print("fused groupby job: OK")


def test_distributed_mapreduce_jobs():
    p = SchemeParams(K=8, P=4, Q=16, N=48, r=2)
    mesh = make_mesh((4, 2), ("rack", "server"))
    rng = np.random.default_rng(1)

    job = histogram_job()
    subfiles = jnp.asarray(rng.integers(0, 1 << 16, size=(p.N, 256)),
                           dtype=jnp.int32)
    ref = run_job(job, subfiles, p, "hybrid")
    # legacy host-round-trip path (the fused default has its own test)
    got = run_job_distributed(job, np.asarray(subfiles), p, mesh, fused=False)
    np.testing.assert_allclose(np.asarray(got.outputs),
                               np.asarray(ref.outputs), rtol=0, atol=0)
    assert got.cross_cost == ref.cross_cost
    print("distributed histogram job (legacy path): OK")

    # the r knob: same job, r=3 replication — same bit-exact outputs,
    # lower cross-rack cost
    got3 = run_job_distributed(job, np.asarray(subfiles), p, mesh, r=3,
                               fused=False)
    np.testing.assert_allclose(np.asarray(got3.outputs),
                               np.asarray(ref.outputs), rtol=0, atol=0)
    assert got3.cross_cost < got.cross_cost
    print("distributed histogram job r=3 knob: OK")

    job = groupby_mean_job()
    rows = jnp.asarray(rng.normal(size=(p.N, 128, 2)) * 100, jnp.float32)
    ref = run_job(job, rows, p, "hybrid")
    got = run_job_distributed(job, np.asarray(rows), p, mesh, fused=False)
    np.testing.assert_allclose(np.asarray(got.outputs),
                               np.asarray(ref.outputs), rtol=1e-5)
    print("distributed groupby job (legacy path): OK")


def test_faulted_recovery_ladder():
    """Crash recovery on the 8-device mesh: for both plan families and
    every r, a mid-shuffle crash recovers to BIT-IDENTICAL outputs via the
    correct ladder rung — decode-around (f <= r-1 per group, nothing
    re-mapped), partial re-map (r=1 orphans), or bounded restart."""
    from repro.resilience import FaultInjector, FaultSpec

    mesh = make_mesh((4, 2), ("rack", "server"))
    rng = np.random.default_rng(7)
    job = histogram_job()

    for family in ("binomial", "resolvable"):
        for r in (1, 2, 3):
            if family == "resolvable" and r != 2:
                continue
            p = SchemeParams(K=8, P=4, Q=16, N=48, r=r)
            subs = np.asarray(rng.integers(0, 1 << 16, size=(p.N, 256)),
                              dtype=np.int32)
            ref = run_job_distributed(job, subs, p, mesh,
                                      scheme_family=family)
            for failed in [(3,), (0, 5)]:
                faults = FaultSpec(FaultInjector.crash(failed))
                got = run_job_distributed(job, subs, p, mesh, faults=faults,
                                          scheme_family=family)
                np.testing.assert_array_equal(np.asarray(got.outputs),
                                              np.asarray(ref.outputs))
                rep = got.recovery
                if r == 1:
                    assert rep.rung == "partial_remap" and rep.n_remapped > 0
                else:
                    assert rep.rung == "decode_around"
                    assert rep.n_remapped == 0
            print(f"faulted recovery {family} r={r}: OK (bit-identical)")

    # unrecoverable first attempt (every server dead) escalates to the
    # restart rung and succeeds on the clean re-run
    p = SchemeParams(K=8, P=4, Q=16, N=48, r=2)
    subs = np.asarray(rng.integers(0, 1 << 16, size=(p.N, 256)),
                      dtype=np.int32)
    ref = run_job_distributed(job, subs, p, mesh)
    faults = FaultSpec(FaultInjector.crash(tuple(range(8))), max_restarts=2)
    got = run_job_distributed(job, subs, p, mesh, faults=faults)
    np.testing.assert_array_equal(np.asarray(got.outputs),
                                  np.asarray(ref.outputs))
    assert got.recovery.rung == "restart" and got.recovery.restarts == 1
    assert len(got.recovery.backoff_delays) == 1
    print("faulted recovery restart rung: OK (bit-identical)")

    # mesh validation fails fast with a legible error
    try:
        bad = make_mesh((2, 4), ("rack", "server"))
        run_job_distributed(job, subs, p, bad)
    except ValueError as e:
        assert "rack=P=4" in str(e)
        print("mesh validation: OK (clear error)")
    else:
        raise AssertionError("mismatched mesh must raise ValueError")


def test_coded_reduce_scatter():
    P_ = 4
    mesh = make_mesh((4, 2), ("rack", "server"))
    G = 64
    rng = np.random.default_rng(2)
    pairs = [(a, b) for a in range(P_) for b in range(a + 1, P_)]
    chunk_grads = rng.normal(size=(len(pairs), G)).astype(np.float32)
    total = chunk_grads.sum(axis=0)

    idx = chunk_index_table(P_)                       # [P, P-1]
    per_rack = chunk_grads[idx]                       # [P, P-1, G]
    # replicate over 'server' axis for the test
    inp = jnp.asarray(np.repeat(per_rack[:, None], 2, axis=1)
                      .reshape(8, P_ - 1, G))

    def body(x):
        return coded_reduce_scatter_r2(x[0], "rack", P_)[None]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(("rack", "server")),),
                       out_specs=P(("rack", "server")))
    out = np.asarray(fn(inp))                          # [8, G/P]
    for rack in range(P_):
        for srv in range(2):
            shard = total.reshape(P_, G // P_)[rack]
            np.testing.assert_allclose(out[rack * 2 + srv], shard, rtol=1e-5)
    print("coded reduce-scatter r=2: OK (== full-batch sum)")

    # the Pallas combine path builds identical send blocks (f(.) as the
    # fused coded_combine encode kernel, interpret mode on CPU)
    def body_pl(x):
        return coded_reduce_scatter_r2(x[0], "rack", P_,
                                       combine_impl="pallas")[None]

    fn_pl = jax.shard_map(body_pl, mesh=mesh,
                          in_specs=(P(("rack", "server")),),
                          out_specs=P(("rack", "server")), check_vma=False)
    np.testing.assert_allclose(np.asarray(fn_pl(inp)), out, rtol=1e-6)
    print("coded reduce-scatter combine_impl=pallas: OK (== xla path)")

    # straggler tolerance: rack 3's data lost; survivors still exact
    def body_f(x):
        return coded_reduce_scatter_r2(x[0], "rack", P_, failed=3)[None]

    fn_f = jax.shard_map(body_f, mesh=mesh,
                         in_specs=(P(("rack", "server")),),
                         out_specs=P(("rack", "server")))
    out_f = np.asarray(fn_f(inp))
    for rack in range(P_ - 1):                         # survivors only
        shard = total.reshape(P_, G // P_)[rack]
        np.testing.assert_allclose(out_f[rack * 2], shard, rtol=1e-5)
    print("coded reduce-scatter with failed rack: OK (erasure-tolerant)")


def test_hierarchical_allreduce():
    mesh = make_mesh((4, 2), ("rack", "server"))
    rng = np.random.default_rng(3)
    x = rng.normal(size=(8, 16)).astype(np.float32)

    def body(v):
        return hierarchical_allreduce(v[0], "server", "rack")[None]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(("rack", "server")),),
                       out_specs=P(("rack", "server")))
    out = np.asarray(fn(jnp.asarray(x)))
    for d in range(8):
        np.testing.assert_allclose(out[d], x.sum(axis=0), rtol=1e-5)
    print("hierarchical all-reduce: OK (== psum)")


if __name__ == "__main__":
    test_distributed_hybrid_shuffle()
    test_coded_multicast_shuffle()
    test_fused_pipeline_parity()
    test_distributed_mapreduce_jobs()
    test_faulted_recovery_ladder()
    test_coded_reduce_scatter()
    test_hierarchical_allreduce()
    print("ALL MULTIDEVICE TESTS PASSED")

"""Compile rehearsals for TPU v5e without a chip.

The TPU compiler compiles for a described ``v5e:2x2`` topology, so these
tests catch what interpret mode cannot: a Pallas kernel that needs more
scoped VMEM than the chip gives, an engine program whose Pallas path
silently falls back to the interpreter, and a one-chip program that does
not fit HBM.  Nothing runs; only compiles.
"""
import os
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.coded_collectives import compile_hybrid_plan
from repro.core.params import SchemeParams
from repro.kernels.bucket_count import (kernel as bc_kernel,
                                       ops as bc_ops)
from repro.kernels.coded_combine import kernel, ops
from repro.mapreduce import engine
from repro.mapreduce.jobs import histogram_job, wide_histogram_job
from repro.obs.tracing import op_key, op_stages

V5E_HBM_BYTES = 15.75 * 2**30          # usable HBM of one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # libtpu would otherwise write its logs outside the checkout
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


def _mesh(topo, shape):
    devs = np.array(topo.devices[:shape[0] * shape[1]]).reshape(shape)
    return Mesh(devs, ("rack", "server"),
                axis_types=(AxisType.Explicit,) * 2)


def _compile_kernel(name, r, d, sharding, T=1024):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    if name == "encode":
        fn = lambda x, c: kernel.encode_pallas(x, c, interpret=False)
        args = (s((r, T, d)), s((r,)))
    elif name == "decode":
        fn = lambda f, k, c: kernel.decode_pallas(f, k, c, interpret=False)
        args = (s((T, d)), s((r - 1, T, d)), s((r,)))
    else:
        fn = lambda x: kernel.xor_encode_pallas(x, interpret=False)
        args = (s((r, T, d), jnp.int32),)
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name", ["encode", "decode", "xor_encode"])
@pytest.mark.parametrize("r,d", [(2, 512), (3, 2048), (2, 4096), (4, 1024)])
def test_coded_combine_compiles_for_v5e(one_chip, name, r, d):
    """A full-d block overran the 16 MiB scoped VMEM at (3, 2048) and
    (2, 4096); the d-tiled grid must fit at every shuffle width."""
    compiled = _compile_kernel(name, r, d, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_engine_pallas_compiles_to_mosaic(topo, no_compile_cache,
                                                monkeypatch):
    """The four-chip (4, 1) mesh at r=3 with the coded Pallas combine holds
    real Mosaic kernels.  The engine picks interpret mode from the
    process's backend (the CPU here), so the test steers it to the chip."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    p = SchemeParams(K=4, P=4, Q=1024, N=96, r=3)
    mesh = _mesh(topo, (4, 1))
    plan = compile_hybrid_plan(p)
    n_loc = plan.local_subfiles.reshape(p.K, -1).shape[1]
    x = jax.ShapeDtypeStruct((p.K, n_loc, 1 << 18), jnp.int32,
                             sharding=NamedSharding(mesh,
                                                    P(("rack", "server"))))
    exe = engine._fused_executable(wide_histogram_job(2048), plan, mesh,
                                   "coded", "pallas")
    text = exe.lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text


@pytest.mark.parametrize("mesh_shape,r,multicast,impl", [
    ((2, 2), 1, "unicast", "xla"), ((4, 1), 2, "coded", "pallas")])
def test_four_chip_engine_counts_on_mosaic(topo, no_compile_cache,
                                           monkeypatch, mesh_shape, r,
                                           multicast, impl):
    """The four-chip programs of the smoke run with the bucket count on the
    MXU kernel, inside a shard_map that checks varying axes (XLA combine)
    and one that does not (Pallas combine)."""
    monkeypatch.setattr(bc_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    p = SchemeParams(K=4, P=mesh_shape[0], Q=1024, N=96, r=r)
    mesh = _mesh(topo, mesh_shape)
    plan = compile_hybrid_plan(p)
    n_loc = plan.local_subfiles.reshape(p.K, -1).shape[1]
    x = jax.ShapeDtypeStruct((p.K, n_loc, 1 << 18), jnp.int32,
                             sharding=NamedSharding(mesh,
                                                    P(("rack", "server"))))
    exe = engine._fused_executable(wide_histogram_job(2048), plan, mesh,
                                   multicast, impl)
    text = exe.lower(x).compile().as_text()
    table = op_stages(text, engine.FUSED_STAGES)
    assert any(table.get(op_key(line)) == "map"
               for line in _entry_ops(text) if "tpu_custom_call" in line)


def test_one_chip_engine_fits_hbm(topo, no_compile_cache, monkeypatch):
    """The one-chip smoke program (K=1, 2 GiB of input) fits one v5e."""
    monkeypatch.setattr(bc_ops, "_on_tpu", lambda: True)
    p = SchemeParams(K=1, P=1, Q=1024, N=512, r=1)
    _assert_fits_hbm(topo, wide_histogram_job(128), p)


def test_wordcount_engine_fits_hbm(topo, no_compile_cache, monkeypatch):
    """The benchmark's word count (Q=1000, 2^25 ids) fits one v5e with the
    kernel's input laid out in rows of 128 ids."""
    monkeypatch.setattr(bc_ops, "_on_tpu", lambda: True)
    p = SchemeParams(K=1, P=1, Q=1000, N=32, r=1)
    _assert_fits_hbm(topo, histogram_job(), p)


def _assert_fits_hbm(topo, job, p):
    """The one-chip fused program for N subfiles of 2^20 ids, its map
    counting on the Mosaic kernel: the arguments and the whole program fit
    one v5e."""
    mesh = _mesh(topo, (1, 1))
    plan = compile_hybrid_plan(p)
    x = jax.ShapeDtypeStruct((1, p.N, 1 << 20), jnp.int32,
                             sharding=NamedSharding(mesh,
                                                    P(("rack", "server"))))
    exe = engine._fused_executable(job, plan, mesh, "unicast", "xla")
    compiled = exe.lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes == p.N * (1 << 20) * 4
    assert total < V5E_HBM_BYTES, total


def _entry_ops(text):
    lines = text[text.index("\nENTRY "):].splitlines()[2:]
    return lines[:lines.index("}")]


def test_wordcount_program_stages_for_v5e(topo, no_compile_cache,
                                          monkeypatch):
    """The one-chip word-count program (HiBench small, 2^25 word ids) as
    the chip's compiler builds it: the map counts its keys in the Mosaic
    bucket-count kernel, labelled ``map``, and no scatter-add is left in
    the map (the only scatter is the stage-1 table fill)."""
    monkeypatch.setattr(bc_ops, "_on_tpu", lambda: True)
    p = SchemeParams(K=1, P=1, Q=1000, N=32, r=1)
    mesh = _mesh(topo, (1, 1))
    x = jax.ShapeDtypeStruct((1, p.N, 1 << 20), jnp.int32,
                             sharding=NamedSharding(mesh,
                                                    P(("rack", "server"))))
    exe = engine._fused_executable(histogram_job(), compile_hybrid_plan(p),
                                   mesh, "unicast", "xla")
    text = exe.lower(x).compile().as_text()
    table = op_stages(text, engine.FUSED_STAGES)
    entry = _entry_ops(text)
    kernels = [op_key(line) for line in entry if "tpu_custom_call" in line]
    assert len(kernels) == 1 and table[kernels[0]] == "map"
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert scatters and all('op_name="jit(device_fn)/stage1/' in line
                            for line in scatters)
    assert not any("kind=kCustom" in line and table.get(op_key(line)) ==
                   "map" for line in entry)
    reduce, = [op_key(line) for line in entry if " reduce(" in line]
    assert table[reduce] == "reduce"


def test_bucket_count_compiles_at_max_q(one_chip):
    """MAX_Q, the largest key range the engine sends to the kernel, fits
    the chip's scoped VMEM."""
    Q = bc_kernel.MAX_Q
    x = jax.ShapeDtypeStruct((2, 1024, bc_kernel.LANES), jnp.int32,
                             sharding=one_chip)
    compiled = jax.jit(lambda a: bc_kernel.bucket_counts_pallas(
        a, Q, interpret=False)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_coded_program_stages_for_v5e(topo, no_compile_cache, monkeypatch):
    """The four-chip coded program with the Pallas codec, as the chip's
    compiler builds it: the exchange is stage 1, the kernels encode and
    decode."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    p = SchemeParams(K=4, P=4, Q=1024, N=96, r=2)
    mesh = _mesh(topo, (4, 1))
    plan = compile_hybrid_plan(p)
    n_loc = plan.local_subfiles.reshape(p.K, -1).shape[1]
    x = jax.ShapeDtypeStruct((p.K, n_loc, 1 << 18), jnp.int32,
                             sharding=NamedSharding(mesh,
                                                    P(("rack", "server"))))
    exe = engine._fused_executable(wide_histogram_job(2048), plan, mesh,
                                   "coded", "pallas")
    text = exe.lower(x).compile().as_text()
    table = op_stages(text, engine.FUSED_STAGES)
    labels = {}
    for line in _entry_ops(text):
        key = op_key(line)
        for what in (" all-to-all", " custom-call"):
            if key.endswith(what):
                labels.setdefault(key.split(".")[0] + what, set()).add(
                    table.get(key))
    assert labels == {"all_to_all all-to-all": {"stage1"},
                      "coded_encode custom-call": {"encode"},
                      "coded_decode custom-call": {"decode"}}
    # the same program, traced on four v5e chips (a kept trace of three
    # jobs): the keys of the compile here name the chip's ops
    busy = _fused_op_seconds(FOUR_CHIP_TRACE, "jit_device_fn(")
    labelled = sum(t for op, t in busy.items() if op_key(op) in table)
    assert labelled >= 0.99 * sum(busy.values()) > 0


FOUR_CHIP_TRACE = (pathlib.Path(__file__).parent / "chipbench"
                   / "wide_hist_coded_4chip.xplane.pb")


def _fused_op_seconds(xplane, module):
    """Device seconds by op name of the ops run inside ``module``'s runs,
    summed over the chips of a kept profiler trace."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        runs = [(e.start_ns, e.start_ns + e.duration_ns)
                for e in lines.get("XLA Modules", ())
                if e.name.startswith(module)]
        for e in lines.get("XLA Ops", ()):
            if any(a <= e.start_ns < b for a, b in runs):
                out[e.name] = out.get(e.name, 0.0) + e.duration_ns / 1e9
    return out


def test_terasort_program_stages_for_v5e(topo, no_compile_cache,
                                         monkeypatch):
    """The four-chip TeraSort program of ``terasort.coded.4chip`` (mesh
    (4, 1), r = 2, XOR-coded stage 1 on the Pallas codec), at a cut record
    count, as the chip's compiler builds it: the codec is the Mosaic
    kernels labelled ``encode`` and ``decode``, the exchange is stage 1,
    the map partitions with a sort and no scatter, and the reducer's sort
    is labelled ``reducer_sort``."""
    from repro.mapreduce.jobs import terasort_job
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    p = SchemeParams(K=4, P=4, Q=32, N=24, r=2)
    mesh = _mesh(topo, (4, 1))
    plan = compile_hybrid_plan(p)
    n_loc = plan.local_subfiles.reshape(p.K, -1).shape[1]
    x = jax.ShapeDtypeStruct((p.K, n_loc, 1024 * 25), jnp.int32,
                             sharding=NamedSharding(mesh,
                                                    P(("rack", "server"))))
    exe = engine._fused_executable(terasort_job(1024, 32, 64), plan, mesh,
                                   "coded_xor", "pallas")
    text = exe.lower(x).compile().as_text()
    table = op_stages(text, engine.FUSED_STAGES)
    labels = {}
    for line in _entry_ops(text):
        key = op_key(line)
        for what in (" all-to-all", " custom-call", " sort", " scatter"):
            if key.endswith(what) and table.get(key):
                labels.setdefault(what.strip(), set()).add(table[key])
    assert labels["all-to-all"] == {"stage1"}
    assert {"encode", "decode"} <= labels["custom-call"]
    assert labels["sort"] == {"map", "reducer_sort"}
    assert "scatter" not in labels or "map" not in labels["scatter"]
    kernels = {op_key(line).split(" = ")[0].rsplit(".", 1)[0]
               for line in _entry_ops(text) if "tpu_custom_call" in line}
    assert {"xor_encode", "xor_decode"} <= kernels

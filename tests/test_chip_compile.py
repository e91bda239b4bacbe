"""Compile rehearsals for TPU v5e without a chip.

The TPU compiler compiles for a described ``v5e:2x2`` topology, so these
tests catch what interpret mode cannot: a Pallas kernel that needs more
scoped VMEM than the chip gives, an engine program whose Pallas path
silently falls back to the interpreter, and a one-chip program that does
not fit HBM.  Nothing runs; only compiles.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.coded_collectives import compile_hybrid_plan
from repro.core.params import SchemeParams
from repro.kernels.coded_combine import kernel, ops
from repro.mapreduce import engine
from repro.mapreduce.jobs import wide_histogram_job

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


def test_one_chip_engine_fits_hbm(topo, no_compile_cache):
    """The one-chip smoke program (K=1, 2 GiB of input) fits one v5e."""
    p = SchemeParams(K=1, P=1, Q=1024, N=512, r=1)
    mesh = _mesh(topo, (1, 1))
    plan = compile_hybrid_plan(p)
    x = jax.ShapeDtypeStruct((1, p.N, 1 << 20), jnp.int32,
                             sharding=NamedSharding(mesh,
                                                    P(("rack", "server"))))
    exe = engine._fused_executable(wide_histogram_job(128), plan, mesh,
                                   "unicast", "xla")
    mem = exe.lower(x).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes == p.N * (1 << 20) * 4
    assert total < V5E_HBM_BYTES, total

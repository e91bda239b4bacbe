"""Jitted training step: microbatch grad accumulation, remat, and the
paper's hybrid-coded data-parallel gradient sync.

Three DP sync modes (TrainConfig.dp_mode):

  * 'dp'          — batch sharded over every data axis; XLA inserts the
                    (hierarchical) gradient all-reduce.  Baseline = the
                    paper's *uncoded* shuffle.
  * 'replicated'  — batch replicated over the 'pod' axis (map replication
                    r = P): every pod computes the full gradient, ZERO
                    cross-pod bytes, P x map FLOPs — the paper's r = P
                    corner of L_cro = (QN/r)(1 - r/P) = 0.
  * 'coded_r2'    — the genuine r = 2 < P scheme, executable: the global
                    batch is split into C(P,2) chunks, chunk {a,b} is
                    mapped by pods a AND b (2 x replication), and the
                    cross-pod stage is the coded reduce-scatter of
                    repro.core.gradient_sync — G(1 - 2/P) cross-pod bytes
                    instead of uncoded G(1 - 1/P), plus single-pod
                    straggler tolerance for free.

The microbatch loop is a jax.lax.scan with fp32 (or bf16) accumulation;
per-layer remat bounds live activations to one microbatch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..core.gradient_sync import coded_reduce_scatter_r2
from ..distributed import sharding as shlib
from ..models import lm
from .optimizer import (OptimizerConfig, adamw_update, init_opt_state,
                        optimizer_update)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    n_microbatches: int = 1
    remat: bool = True
    remat_blocks: int = 1             # 2-level remat (sqrt-L memory)
    scan_layers: bool = True
    unroll_scans: bool = False        # dry-run cost extraction only
    dp_mode: str = "dp"               # dp | replicated | coded_r2
    grad_dtype: Any = jnp.float32     # accumulation dtype
    aux_coef: float = 0.01
    dense_moe: bool = False           # exact dispatch (tiny configs)
    moe_groups: int = 1               # sort-dispatch groups (= dp shards)
    mixer_chunk: int = 64
    opt: OptimizerConfig = OptimizerConfig()


def init_train_state(key: jax.Array, cfg: ArchConfig, tc: TrainConfig,
                     param_dtype=jnp.float32) -> Dict:
    params = lm.init_params(key, cfg, param_dtype)
    return {"params": params, "opt": init_opt_state(params, tc.opt),
            "step": jnp.zeros((), jnp.int32)}


def _split_micro(batch: Dict, n: int) -> Dict:
    def f(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape(n, b // n, *x.shape[1:])
    return jax.tree.map(f, batch)


def _loss_fn(params, cfg: ArchConfig, tc: TrainConfig, mb: Dict):
    return lm.lm_loss(params, cfg, mb, aux_coef=tc.aux_coef,
                      scan_layers=tc.scan_layers, remat=tc.remat,
                      dense_moe=tc.dense_moe, mixer_chunk=tc.mixer_chunk,
                      unroll_scans=tc.unroll_scans,
                      remat_blocks=tc.remat_blocks, moe_groups=tc.moe_groups)


def _grad_constraint(params):
    """Pin gradient sharding to the (FSDP-overlaid) parameter specs so the
    scan-over-microbatches accumulator is reduce-scattered per step instead
    of living unsharded over the data axis (a 16x HBM cliff at 405B)."""
    pol = shlib.active_policy()
    if pol is None:
        return lambda g: g
    fsdp = pol.rules.get("fsdp") is not None
    specs = shlib.param_pspecs(params, pol, fsdp=fsdp)

    def constrain(g):
        return jax.tree.map(
            lambda leaf, s: jax.lax.with_sharding_constraint(
                leaf, jax.sharding.NamedSharding(pol.mesh, s)),
            g, specs, is_leaf=lambda x: not isinstance(x, (dict, list)))
    return constrain


def accumulate_grads(params, cfg: ArchConfig, tc: TrainConfig,
                     batch: Dict) -> Tuple[Any, jax.Array]:
    """Microbatch-scanned grad accumulation.  Returns (grads, mean loss)."""
    n = tc.n_microbatches
    constrain = _grad_constraint(params)
    if n == 1:
        (loss, _), grads = jax.value_and_grad(
            _loss_fn, has_aux=True)(params, cfg, tc, batch)
        return constrain(grads), loss
    micro = _split_micro(batch, n)
    g0 = constrain(jax.tree.map(lambda p: jnp.zeros(p.shape, tc.grad_dtype),
                                params))

    def body(carry, mb):
        acc, loss_sum = carry
        (loss, _), grads = jax.value_and_grad(
            _loss_fn, has_aux=True)(params, cfg, tc, mb)
        acc = constrain(jax.tree.map(
            lambda a, g: a + g.astype(tc.grad_dtype), acc, constrain(grads)))
        return (acc, loss_sum + loss), None

    (grads, loss_sum), _ = jax.lax.scan(body, (g0, jnp.zeros(())), micro)
    grads = jax.tree.map(lambda g: (g / n).astype(tc.grad_dtype), grads)
    return constrain(grads), loss_sum / n


# ---------------------------------------------------------------------------
# coded_r2: chunked batch layout + shard_map coded sync over 'pod'
# ---------------------------------------------------------------------------

def chunk_layout_r2(global_batch: int, P_: int) -> Tuple[int, int]:
    """(n_chunks, rows per chunk) for the C(P,2)-chunk layout."""
    n_chunks = P_ * (P_ - 1) // 2
    assert global_batch % n_chunks == 0, (global_batch, n_chunks)
    return n_chunks, global_batch // n_chunks


def make_coded_batch_r2(batch: Dict, P_: int) -> Dict:
    """Reorder a [B, ...] batch into the replicated chunk layout
    [P, P-1, B/C(P,2), ...]: row p holds the P-1 chunks pod p maps
    (each chunk appears in exactly its 2 member pods)."""
    from ..core.gradient_sync import chunk_index_table
    table = chunk_index_table(P_)                 # [P, P-1] chunk ids

    def f(x):
        n_chunks, rows = chunk_layout_r2(x.shape[0], P_)
        xc = x.reshape(n_chunks, rows, *x.shape[1:])
        return xc[table]                          # [P, P-1, rows, ...]
    return jax.tree.map(f, batch)


def coded_grads_r2(params, cfg: ArchConfig, tc: TrainConfig,
                   coded_batch: Dict, mesh: Mesh, pod_axis: str = "pod",
                   failed: Optional[int] = None) -> Tuple[Any, jax.Array]:
    """Gradient computation + coded cross-pod sync (r = 2).

    coded_batch: the [P, P-1, rows, ...] layout of make_coded_batch_r2,
    sharded over 'pod' on axis 0.  Every pod maps its P-1 chunks (the 2x
    map replication), then the coded reduce-scatter + all-gather restores
    the exact full-batch mean gradient — with any single ``failed`` pod's
    contribution recoverable from its pair partners.
    """
    P_ = mesh.shape[pod_axis]
    flat_params, tree = jax.tree.flatten(params)
    sizes = [int(np.prod(p.shape)) for p in flat_params]
    G = sum(sizes)
    pad = (-G) % P_

    other_axes = [a for a in mesh.axis_names if a != pod_axis]

    def pod_fn(pb, *ps):
        params_l = jax.tree.unflatten(tree, list(ps))
        pb = jax.tree.map(lambda x: x[0], pb)     # [P-1, rows, ...]

        def chunk_grads(mb):
            (loss, _), grads = jax.value_and_grad(
                _loss_fn, has_aux=True)(params_l, cfg, tc, mb)
            vec = jnp.concatenate(
                [g.astype(tc.grad_dtype).ravel()
                 for g in jax.tree.leaves(grads)])
            return jnp.pad(vec, (0, pad)), loss

        def body(loss_sum, mb):
            vec, loss = chunk_grads(mb)
            return loss_sum + loss, vec
        loss_sum, vecs = jax.lax.scan(body, jnp.zeros(()), pb)
        # [P-1, G+pad] per-chunk grad partials, partner-ascending order
        shard = coded_reduce_scatter_r2(vecs, pod_axis, P_, failed=failed)
        full = jax.lax.all_gather(shard, pod_axis, axis=0, tiled=True)
        n_chunks = P_ * (P_ - 1) // 2
        full = full / n_chunks                    # mean over chunks
        loss = loss_sum / (P_ - 1)
        # replica-mean over non-pod axes is a no-op (identical) but keeps
        # the result uniform across the mesh for pjit consumers
        return full[None], loss[None]

    in_spec = (P(pod_axis),) + tuple(P() for _ in flat_params)
    fn = jax.shard_map(pod_fn, mesh=mesh, in_specs=in_spec,
                       out_specs=(P(pod_axis), P(pod_axis)),
                       check_vma=False)
    full, loss = fn(jax.tree.map(lambda x: x, coded_batch), *flat_params)
    vec = full[0]                                 # identical across pods
    loss = loss.mean()
    # unflatten
    out, off = [], 0
    for p, s in zip(flat_params, sizes):
        out.append(vec[off:off + s].reshape(p.shape).astype(tc.grad_dtype))
        off += s
    return jax.tree.unflatten(tree, out), loss


# ---------------------------------------------------------------------------
# The jitted step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, tc: TrainConfig,
                    mesh: Optional[Mesh] = None,
                    policy: Optional[shlib.ShardingPolicy] = None,
                    donate: bool = True) -> Callable:
    """Build step(state, batch) -> (state, metrics).

    For dp_mode='coded_r2', ``batch`` must be in make_coded_batch_r2
    layout and ``mesh`` must be provided.
    """

    def step(state, batch):
        with shlib.use_policy(policy):
            if tc.dp_mode == "coded_r2":
                grads, loss = coded_grads_r2(state["params"], cfg, tc,
                                             batch, mesh)
            else:
                grads, loss = accumulate_grads(state["params"], cfg, tc,
                                               batch)
            new_params, new_opt, om = optimizer_update(
                grads, state["opt"], state["params"], tc.opt)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": loss, **om}
        return new_state, metrics

    if mesh is None:
        return jax.jit(step, donate_argnums=(0,) if donate else ())
    return step     # caller jits with explicit shardings (launch/dryrun)


def train_step_shardings(state, batch, policy: shlib.ShardingPolicy,
                         fsdp: bool = True):
    """(in_shardings, out_shardings) trees for jitting make_train_step's
    step under pjit on a production mesh."""
    mesh = policy.mesh
    pspec = shlib.param_pspecs(state["params"], policy, fsdp=fsdp)
    opt_spec = {"m": pspec, "v": pspec, "count": P()}
    state_spec = {"params": pspec, "opt": opt_spec, "step": P()}
    batch_spec = shlib.batch_pspecs(policy, batch)
    to_sh = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))
    metrics_spec = {"loss": P(), "grad_norm": P(), "lr": P()}
    return ((to_sh(state_spec), to_sh(batch_spec)),
            (to_sh(state_spec), to_sh(metrics_spec)))

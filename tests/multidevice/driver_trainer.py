"""Subprocess driver: trainer-level multi-pod paths — coded_r2 training
steps on a (pod, data) mesh, hierarchical collectives, and the dry-run
machinery on a miniature mesh.  Spawned by tests/test_multidevice.py."""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import numpy as np                                             # noqa: E402
import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
from jax.sharding import PartitionSpec as P                    # noqa: E402

from repro.configs import ARCHS                                # noqa: E402
from repro.data.pipeline import SyntheticPipeline              # noqa: E402
from repro.distributed.collectives import (                    # noqa: E402
    flat_all_to_all, hierarchical_all_to_all)
from repro.distributed.meshes import make_mesh                 # noqa: E402
from repro.train.optimizer import OptimizerConfig              # noqa: E402
from repro.train.trainer import (TrainConfig,                  # noqa: E402
                                 init_train_state,
                                 make_coded_batch_r2, make_train_step)

CFG = ARCHS["qwen2-1.5b"].reduced()


def test_coded_r2_training_descends():
    mesh = make_mesh((4, 2), ("pod", "data"))
    tc = TrainConfig(remat=False, dense_moe=True, dp_mode="coded_r2",
                     opt=OptimizerConfig(lr=1e-3, warmup_steps=2,
                                         decay_steps=30))
    state = init_train_state(jax.random.PRNGKey(0), CFG, tc)
    pipe = SyntheticPipeline(CFG, global_batch=12, seq_len=24)
    step = jax.jit(make_train_step(CFG, tc, mesh=mesh, donate=False))
    losses = []
    for i in range(6):
        cb = make_coded_batch_r2(pipe.batch_at(i), 4)
        state, m = step(state, cb)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    print("coded_r2 training descends:", [f"{l:.3f}" for l in losses])


def test_hierarchical_a2a_equals_flat():
    mesh = make_mesh((2, 4), ("pod", "data"))
    x = jnp.arange(8 * 8 * 6, dtype=jnp.float32).reshape(8, 8, 6)

    def run(fn):
        f = jax.shard_map(lambda a: fn(a[0])[None], mesh=mesh,
                          in_specs=(P(("pod", "data")),),
                          out_specs=P(("pod", "data")))
        return np.asarray(f(x))
    h = run(lambda a: hierarchical_all_to_all(a, "data", "pod"))
    fl = run(lambda a: flat_all_to_all(a, "data", "pod"))
    np.testing.assert_array_equal(h, fl)
    print("hierarchical a2a == flat a2a")


def test_sequence_tp_loss_unchanged():
    """Megatron-SP sharding must not change the math."""
    from repro.distributed import sharding as shlib
    from repro.models import lm
    from repro.models.frontends import make_train_batch
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ARCHS["granite-3-2b"].reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    batch = make_train_batch(jax.random.PRNGKey(1), cfg, batch=2, seq=16)
    l_ref, _ = lm.lm_loss(params, cfg, batch)
    rules = shlib.with_sequence_tp(shlib.default_rules(multi_pod=False))
    pol = shlib.ShardingPolicy(mesh, rules)
    with mesh:
        with shlib.use_policy(pol):
            l_sp, _ = jax.jit(lambda p, b: lm.lm_loss(p, cfg, b))(params,
                                                                  batch)
    assert abs(float(l_ref) - float(l_sp)) < 1e-4, (l_ref, l_sp)
    print(f"sequence-TP loss identical: {float(l_ref):.5f}")


if __name__ == "__main__":
    test_coded_r2_training_descends()
    test_hierarchical_a2a_equals_flat()
    test_sequence_tp_loss_unchanged()
    print("ALL TRAINER MULTIDEVICE TESTS PASSED")

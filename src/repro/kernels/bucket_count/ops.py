"""Public bucket count: the MXU kernel on TPU, the scatter-add elsewhere.

:func:`bucket_counts` is what the counting jobs' map functions call.  It
takes the Pallas kernel where it compiles to Mosaic (a TPU backend) and
the key range fits its VMEM working set (``kernel.MAX_Q``); anywhere else
it takes the plain scatter of ``ref.py``, so CPU hosts keep their path.
Each trace counts its path once in ``bucket_count_programs_total``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...obs import metrics as obs_metrics
from . import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def bucket_counts_mxu(bucket: jax.Array, Q: int) -> jax.Array:
    """bucket: [..., T] int keys in [0, Q) -> [..., Q] int32 counts, by the
    kernel (interpreted off TPU)."""
    from . import kernel
    lead, T = bucket.shape[:-1], bucket.shape[-1]
    x = bucket.astype(jnp.int32).reshape(-1, T)
    pad = (-T) % kernel.LANES
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=-1)
    out = kernel.bucket_counts_pallas(
        x.reshape(x.shape[0], -1, kernel.LANES), Q, interpret=not _on_tpu())
    return out.reshape(*lead, -1)[..., :Q]


def bucket_counts(bucket: jax.Array, Q: int, dtype=jnp.int32) -> jax.Array:
    """bucket: [..., T] keys in [0, Q) -> [..., Q] counts in ``dtype``."""
    impl = "scatter"
    if _on_tpu():
        from . import kernel    # Pallas: imported where the kernel can run
        if Q <= kernel.MAX_Q:
            impl = "mxu"
    obs_metrics.counter(
        "bucket_count_programs_total",
        "traced bucket counts (one per compiled program), by path").inc(
            impl=impl)
    if impl == "mxu":
        return bucket_counts_mxu(bucket, Q).astype(dtype)
    return ref.scatter_counts(bucket, Q, dtype)

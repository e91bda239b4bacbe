"""The XOR codec (``kernels/coded_combine``) as a share of its HBM
roofline, in %: the logical bytes that stage 1's encode and decode must
move per chip per job (:func:`codec_bytes`), over the device time of the
ops that the fused program labels ``encode`` or ``decode``
(``repro.mapreduce.engine.fused_op_stages``: the gather of the packet
components and the kernels), mean over chips, per job, at the chip's
peak HBM bandwidth.  A program without the stage table, or with no op so
labelled, reads nothing."""

# peak HBM bytes/s by device kind (Google Cloud documentation, "TPU v5e":
# 16 GB of HBM at 819 GB/s)
PEAK_HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def codec_bytes(cell):
    """Logical bytes of one job's encode and decode on one chip, padding
    not counted: each of the two reads ``arity`` streams and writes one,
    of T rows x d words (T = n_send x Q packet rows of the cell's plan, d
    the job's payload width): 2 (arity + 1) T d itemsize.  None where the
    program cannot build the cell's job."""
    import numpy as np
    from repro.core.coded_collectives import compile_hybrid_plan
    from repro.core.params import SchemeParams
    from repro.mapreduce import jobs
    wl, cfg = cell.workload, cell.config
    factory = getattr(jobs, cfg["job"], None)
    if factory is None:
        return None
    plan = compile_hybrid_plan(SchemeParams(
        K=wl["mesh"][0] * wl["mesh"][1], P=wl["mesh"][0], Q=cfg["Q"],
        N=cfg["N"], r=wl["r"]))
    rows = plan.n_send * cfg["Q"]
    d = factory(**cfg["job_args"]).d
    itemsize = np.dtype(cfg["dtype"]).itemsize
    return 2 * (plan.mcast_arity + 1) * rows * d * itemsize


def read(w):
    s = w.trace
    if s is None or not s.window_ns or not w.jobs:
        return None
    try:
        from repro.mapreduce.engine import fused_op_stages
        from repro.obs.tracing import op_key
    except ImportError:
        return None
    stages = fused_op_stages()
    ops = s.ops(lambda text: stages.get(op_key(text)) in ("encode",
                                                          "decode"))
    seconds = sum(t for _, t, _ in ops) / s.chips / w.jobs
    nbytes = codec_bytes(w.cell) if seconds else None
    if not nbytes:
        return None
    return 100.0 * nbytes / seconds / PEAK_HBM_BYTES_PER_S[w.device_kind]

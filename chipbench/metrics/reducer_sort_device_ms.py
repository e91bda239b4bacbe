"""Reducer sort: device time of the ops that the fused program's own
compiled module puts under the ``reducer_sort`` named scope
(``repro.mapreduce.engine.fused_op_stages``; ``terasort_job``'s ordering
of its records and the gather of the rows into that order), summed over
the window, mean over chips, per job, in ms.  A program without the
stage, or with no op so labelled, reads nothing."""


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
    ops = s.ops(lambda text: stages.get(op_key(text)) == "reducer_sort")
    if not ops:
        return None
    return sum(t for _, t, _ in ops) / s.chips / w.jobs * 1e3

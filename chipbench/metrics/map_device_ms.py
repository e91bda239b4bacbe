"""Map (the scatter-add): device time of the ops that the fused program's
own compiled module puts under its ``map`` named scope
(``repro.mapreduce.engine.fused_op_stages``), summed over the window, mean
over chips, per job, in ms.  An op that XLA fused across the map and
another stage carries a joint label (``map+reduce``) and is not counted.
A program without the stage table reads nothing."""


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
    if not stages:
        return None
    ops = s.ops(lambda text: stages.get(op_key(text)) == "map")
    return sum(t for _, t, _ in ops) / s.chips / w.jobs * 1e3

"""Cross-rack coded shuffle: device time of the ops that the fused
program's own compiled module puts under its ``stage1``, ``encode`` or
``decode`` named scopes, or under a joint label made of these alone
(``repro.mapreduce.engine.fused_op_stages``), summed over the window, mean
over chips, per job, in ms.  A program without the stage table, or with
no op so labelled, reads nothing."""

STAGES = {"stage1", "encode", "decode"}


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

    def shuffle(text):
        label = stages.get(op_key(text))
        return label is not None and set(label.split("+")) <= STAGES
    ops = s.ops(shuffle)
    if not ops:
        return None
    return sum(t for _, t, _ in ops) / s.chips / w.jobs * 1e3

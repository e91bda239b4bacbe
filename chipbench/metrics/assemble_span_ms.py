"""Output assembly (``engine.assemble_outputs``: the reduce rows gathered
into global key order): the engine's ``assemble`` span per job, in ms.  It
ends when the assembled outputs are ready (a wait the engine makes only
while tracing).  A program without the span reads nothing."""


def read(w):
    t = w.spans.get("assemble")
    return None if t is None or not w.jobs else t / w.jobs * 1e3

"""Host-to-device upload: the engine's ``upload`` span per job, in ms.  The
span follows ``pack`` and ends when the job's packed input is on its
devices (a wait the engine makes only while tracing).  A program without
the span reads nothing."""


def read(w):
    t = w.spans.get("upload")
    return None if t is None or not w.jobs else t / w.jobs * 1e3

"""Engine host path (``pack_local_subfiles`` + ``put_per_device``): the
engine's ``pack`` span per job, in ms.  The span covers the host gather of
each device's subfiles and the call that starts their upload, not the
upload's completion."""


def read(w):
    t = w.spans.get("pack")
    return None if t is None or not w.jobs else t / w.jobs * 1e3

"""Fused device program (map, shuffle, reduce in one jitted shard_map):
the engine's ``map_shuffle_reduce`` span per job, in ms.  It ends at
``block_until_ready`` and includes waiting for the input upload."""


def read(w):
    t = w.spans.get("map_shuffle_reduce")
    return None if t is None or not w.jobs else t / w.jobs * 1e3

"""D2H: `fetch_shards` counter growth over the window / frames: per-shard
concurrent transfers issued (0 = every fetch was one blocking get)."""

from tvtbench import evidence


def read(ev):
    return evidence.per_frame(ev, "fetch_shards")

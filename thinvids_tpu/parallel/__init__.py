"""Sequence (GOP) parallelism over a TPU device mesh.

The reference shards the video timeline into ~10 MB file segments dispatched
to worker nodes over a task queue (/root/reference/worker/tasks.py:597-609,
977-1052); here the timeline is sharded at closed-GOP boundaries across the
devices of a `jax.sharding.Mesh` with `shard_map`, and encoded segments are
re-assembled in index order (the stitcher analog, tasks.py:2047-2069).

Imports are lazy: the coordinator's control plane imports `planner`
from this package (cluster/executor, cluster/remote) WITHOUT dragging
dispatch's jax dependency in.
"""

__all__ = ["plan_segments", "GopShardEncoder", "encode_clip_sharded"]


def __getattr__(name):
    if name == "plan_segments":
        from .planner import plan_segments

        return plan_segments
    if name in ("GopShardEncoder", "encode_clip_sharded"):
        from . import dispatch

        return getattr(dispatch, name)
    raise AttributeError(name)

"""segment: `stage_ms.scenecut` growth over the window / frames (host
thread time of the executor's look for scene cuts: one read of the
source's luma, block sums, the two costs per frame; it runs in each
job's lead-in, before the first wave is staged). Not measured where
the program has no such stage."""

from tvtbench import evidence


def read(ev):
    if "scenecut" not in ev["snapshot"]["after"]:
        return None
    return evidence.per_frame(ev, "scenecut")

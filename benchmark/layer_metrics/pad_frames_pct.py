"""staging: the share of the frames GOP waves staged that were repeats
the host drops again (a GOP shorter than the program's 32 frames is
staged with its last frame repeated; a wave short of a GOP per device
repeats a whole GOP): growth of the counter `pad_frames` / growth of
`wave_frames` x 100 over the window. What cut-aligned GOPs pay on the
device. Not measured where the program has no such counters or
neither moved."""

from tvtbench import evidence


def read(ev):
    staged = evidence.stage_delta(ev, "wave_frames")
    if staged <= 0:
        return None
    return 100.0 * evidence.stage_delta(ev, "pad_frames") / staged

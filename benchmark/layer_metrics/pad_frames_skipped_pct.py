"""device program: the share of the repeats GOP waves staged that no
program encoded (a plan made on scene cuts hands its waves each GOP's
real length, and the P-frame loop stops there): growth of the counter
`pad_frames_skipped` / growth of `pad_frames` x 100 over the window.
100 where the bounded loop ran every wave, 0 where the repeats were
encoded and dropped; `pad_frames_pct` beside it says what was STAGED.
Not measured where the program has no such counter or no repeat was
staged."""

from tvtbench import evidence


def read(ev):
    if "pad_frames_skipped" not in ev["snapshot"]["after"]:
        return None
    staged = evidence.stage_delta(ev, "pad_frames")
    if staged <= 0:
        return None
    return 100.0 * evidence.stage_delta(ev, "pad_frames_skipped") / staged

"""Source clips: generator planes -> a raw .y4m on disk, and back.

The file is the job's input (hard-linked under one name per job) and
the ground truth the decoded outputs are compared with, read back by
offset so a clip never sits in the parent's memory."""

import os

import numpy as np

FRAME_MARK = b"FRAME\n"


def header(width, height, fps=30):
    return (f"YUV4MPEG2 W{width} H{height} F{fps}:1 Ip A1:1 "
            f"C420jpeg\n").encode()


def frame_bytes(width, height):
    return width * height + 2 * (width // 2) * (height // 2)


def clip_size(width, height, frames):
    return len(header(width, height)) \
        + frames * (len(FRAME_MARK) + frame_bytes(width, height))


def write_clip(path, generator, params, frames, width, height, seed):
    """Write the clip unless a whole one is already there (the name
    carries generator, shape, length and seed). Returns True when it
    was generated in this call."""
    if os.path.exists(path) \
            and os.path.getsize(path) == clip_size(width, height, frames):
        return False
    tmp = path + ".tmp"
    with open(tmp, "wb") as fp:
        fp.write(header(width, height))
        for y, u, v in generator.planes(frames, width, height, seed,
                                        **params):
            fp.write(FRAME_MARK)
            fp.write(np.ascontiguousarray(y).tobytes())
            fp.write(np.ascontiguousarray(u).tobytes())
            fp.write(np.ascontiguousarray(v).tobytes())
    os.replace(tmp, path)
    return True


def cut_prefix(src, dst, frames, width, height):
    """The first `frames` frames of `src` as a clip of its own."""
    left = clip_size(width, height, frames)
    with open(src, "rb") as fin, open(dst + ".tmp", "wb") as fout:
        while left:
            chunk = fin.read(min(left, 1 << 24))
            if not chunk:
                raise ValueError(f"{src} is shorter than {frames} frames")
            fout.write(chunk)
            left -= len(chunk)
    os.replace(dst + ".tmp", dst)


def luma(path, index, width, height):
    """Luma plane of frame `index` of a clip written by write_clip."""
    offset = len(header(width, height)) + index * (
        len(FRAME_MARK) + frame_bytes(width, height)) + len(FRAME_MARK)
    return np.memmap(path, np.uint8, "r", offset, (height, width))

"""Just enough ISO BMFF to look at an output without the program's own
reader: the `mdat` payload (the video bytes; the outputs carry no audio
track) and the length-prefixed NAL units inside it."""

import hashlib
import struct


def boxes(data, start=0, end=None):
    """(type, payload_start, payload_end) of each box in data[start:end]."""
    end = len(data) if end is None else end
    pos = start
    while pos + 8 <= end:
        size, kind = struct.unpack(">I4s", data[pos:pos + 8])
        head = 8
        if size == 1:
            size = struct.unpack(">Q", data[pos + 8:pos + 16])[0]
            head = 16
        elif size == 0:
            size = end - pos
        if size < head:
            raise ValueError(f"bad box size {size} at {pos}")
        yield kind.decode("latin-1"), pos + head, pos + size
        pos += size


def mdat(data):
    """The media payload of an MP4's bytes."""
    for kind, lo, hi in boxes(data):
        if kind == "mdat":
            return memoryview(data)[lo:hi]
    raise ValueError("no mdat box")


def video_digest(path):
    """(video bytes, sha256 of them) of an output file."""
    with open(path, "rb") as fp:
        payload = mdat(fp.read())
    return len(payload), hashlib.sha256(payload).hexdigest()


def vcl_nals(payload, count):
    """The first `count` slice NAL units (types 1 and 5) of an mdat
    payload of 4-byte length-prefixed NALs, as bytes."""
    out, pos = [], 0
    while pos + 4 <= len(payload) and len(out) < count:
        n = struct.unpack(">I", payload[pos:pos + 4])[0]
        nal = bytes(payload[pos + 4:pos + 4 + n])
        if nal and (nal[0] & 0x1F) in (1, 5):
            out.append(nal)
        pos += 4 + n
    return out

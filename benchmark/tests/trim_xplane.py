"""Trim an .xplane.pb at the protobuf wire level: keep the device planes
and 'Task Environment', and of every line the events A..B.
usage: trim_xplane.py IN OUT A B   (event indices A <= i < B of every line)"""
import sys


def varint(buf, pos):
    shift = val = 0
    while True:
        b = buf[pos]; pos += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, pos
        shift += 7


def enc_varint(v):
    out = bytearray()
    while True:
        b = v & 0x7F; v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b); return bytes(out)


def fields(buf):
    pos = 0
    while pos < len(buf):
        start = pos
        key, pos = varint(buf, pos)
        num, wt = key >> 3, key & 7
        if wt == 0:
            _, pos = varint(buf, pos); payload = None
        elif wt == 1:
            pos += 8; payload = None
        elif wt == 5:
            pos += 4; payload = None
        elif wt == 2:
            n, pos = varint(buf, pos); payload = buf[pos:pos + n]; pos += n
        else:
            raise ValueError(wt)
        yield num, wt, payload, buf[start:pos]


def ld(num, payload):
    return enc_varint(num << 3 | 2) + enc_varint(len(payload)) + payload


def trim_line(buf, n):
    """n = (a, b): keep the events with index a <= i < b."""
    out, i = bytearray(), 0
    for num, wt, payload, raw in fields(buf):
        if num == 4 and wt == 2:
            if n[0] <= i < n[1]:
                out += raw
            i += 1
        else:
            out += raw
    return bytes(out)


def plane_name(buf):
    for num, wt, payload, raw in fields(buf):
        if num == 2 and wt == 2:
            return bytes(payload).decode()
    return ""


def first_varint_field(buf, want):
    for num, wt, payload, raw in fields(buf):
        if num == want and wt == 0:
            return varint(raw, 1)[0]
    return 0


def trim_plane(buf, n):
    """Lines cut to n events; event metadata kept only where a kept
    event names it (the op line's metadata is whole HLO instructions)."""
    lines, used = [], set()
    for num, wt, payload, raw in fields(buf):
        if num == 3 and wt == 2:
            line = trim_line(payload, n)
            lines.append(line)
            for lnum, lwt, lpayload, lraw in fields(line):
                if lnum == 4 and lwt == 2:
                    used.add(first_varint_field(lpayload, 1))
    out = bytearray()
    lines = iter(lines)
    for num, wt, payload, raw in fields(buf):
        if num == 3 and wt == 2:
            out += ld(3, next(lines))
        elif num == 4 and wt == 2:
            if first_varint_field(payload, 1) in used:
                out += raw
        else:
            out += raw
    return bytes(out)


def main(src, dst, n):
    data = memoryview(open(src, "rb").read())
    out = bytearray()
    for num, wt, payload, raw in fields(data):
        if num == 1 and wt == 2:
            name = plane_name(payload)
            if name.startswith("/device:") or name == "Task Environment":
                out += ld(1, trim_plane(payload, n))
        else:
            out += raw
    open(dst, "wb").write(out)
    print(len(data), "->", len(out))


main(sys.argv[1], sys.argv[2], (int(sys.argv[3]), int(sys.argv[4])))

"""Cut an .xplane.pb down to what `tvtbench/host_reduce.py` reads, at the
protobuf wire level: of the host plane the `tvt:*` events (and their
metadata) of every line, of the FIRST device plane that ran ops the
outermost events of its op line without any metadata (an op nested in a
`while` adds nothing to the union of busy time), and `Task Environment`
whole. Every line keeps its name and its own time base.

usage: trim_host_xplane.py IN OUT"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tvtbench import host_reduce as hr      # noqa: E402
from tvtbench import scope_reduce as sr     # noqa: E402


def enc_varint(v):
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def field(num, value):
    if isinstance(value, int):
        return enc_varint(num << 3) + enc_varint(value)
    return enc_varint(num << 3 | 2) + enc_varint(len(value)) + bytes(value)


def event(meta_id, offset, duration):
    return field(4, field(1, meta_id) + field(2, offset)
                 + field(3, duration))


def line_head(line):
    """The line's name and time base, re-encoded."""
    return b"".join(field(num, val) for num, val in sr.fields(line)
                    if num in (2, 3))


def outermost(events):
    """The events no other event of the line encloses."""
    out, end = [], -1
    for meta_id, off, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        if off + dur > end:
            out.append((meta_id, off, dur))
            end = off + dur
    return out


def main(src, dst):
    with open(src, "rb") as fp:
        data = fp.read()
    out, device_done = bytearray(), False
    for num, plane in sr.fields(data):
        if num != 1:
            continue
        name, lines, metadata, _stats = hr.plane_parts(plane)
        if name == hr.ENV_PLANE:
            out += field(1, plane)
        elif name == hr.HOST_PLANE:
            wanted = hr.annotation_names(metadata)
            body = field(2, name.encode()) + b"".join(
                field(4, field(1, k) + field(2, field(1, k)
                                               + field(2, n.encode())))
                for k, n in sorted(wanted.items()))
            for line in lines:
                _base, events = hr.line_events(line, wanted)
                if events:
                    body += field(3, line_head(line) + b"".join(
                        event(*e) for e in events))
            out += field(1, body)
        elif name.startswith("/device:") and not device_done:
            body = field(2, name.encode())
            for line in lines:
                if sr._line_name(line) != sr.OP_LINE:
                    continue
                events = outermost(hr.line_events(line)[1])
                device_done = device_done or bool(events)
                body += field(3, line_head(line) + b"".join(
                    event(*e) for e in events))
            if device_done:
                out += field(1, body)
    with open(dst, "wb") as fp:
        fp.write(out)
    print(len(data), "->", len(out))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])

"""What a profiler trace knows about each operation beyond its name.

``jax.profiler.ProfileData`` gives an event's own stats only (offset and
duration on the device).  The file holds more, once per distinct
operation: every plane's ``event_metadata`` carries ``tf_op`` (the HLO
instruction's ``op_name``, the path of JAX transforms, flax modules and
primitive it was traced under:
``jit(wrapped)/transpose(jvp(TransformerLM))/Block_0/Dense_0/dot_general``),
``hlo_category``, ``flops`` and ``bytes_accessed``.  This reads them with
the standard library alone, straight off the protobuf wire format:

    XSpace.planes = 1
    XPlane.name = 2, .lines = 3 (skipped by length), .event_metadata = 4,
        .stat_metadata = 5           (both maps: entry key = 1, value = 2)
    XEventMetadata.name = 2, .stats = 5
    XStat.metadata_id = 1, .uint64_value = 3, .int64_value = 4,
        .str_value = 5       (all that the stats in ``KEEP`` use on a v5e)
    XStatMetadata.name = 2

``load(path)`` -> ``{plane name: {event name: {stat: value}}}`` for the
stats in ``KEEP``; an event name is what ``ProfileData`` calls
``event.name`` (on a TPU's "XLA Ops" line, the instruction's HLO text).
"""

from __future__ import annotations

import gzip

KEEP = ("tf_op", "hlo_category", "flops", "bytes_accessed")

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def fields(buf):
    """One message's ``(field number, wire type, value)`` triples: an int
    for a varint, a memoryview for anything with a length."""
    i, n = 0, len(buf)
    while i < n:
        key, i = varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value, i = varint(buf, i)
        elif wire == BYTES:
            size, i = varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == FIXED64:
            value, i = buf[i:i + 8], i + 8
        elif wire == FIXED32:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield number, wire, value


def varint(buf, i):
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def map_entries(entries):
    """A ``map<int64, Message>`` field's entries -> ``{key: value bytes}``."""
    out = {}
    for entry in entries:
        key, value = 0, b""
        for number, _, v in fields(entry):
            if number == 1:
                key = v
            elif number == 2:
                value = v
        out[key] = value
    return out


def stat_value(stat, stat_names):
    """One XStat -> ``(stat name, value)``; a value of another kind than
    a whole number or a string is None."""
    name, value = None, None
    for number, _, v in fields(stat):
        if number == 1:
            name = stat_names.get(v)
        elif number in (3, 4):
            value = v
        elif number == 5:
            value = str(v, "utf-8", "replace")
    return name, value


def plane_meta(plane):
    """One XPlane -> ``(name, {event name: {stat: value}})``."""
    name, events, stats = "", [], []
    for number, _, v in fields(plane):
        if number == 2:
            name = str(v, "utf-8")
        elif number == 4:
            events.append(v)
        elif number == 5:
            stats.append(v)
    stat_names = {
        key: next((str(v, "utf-8") for n, _, v in fields(meta) if n == 2),
                  "")
        for key, meta in map_entries(stats).items()}
    out = {}
    for meta in map_entries(events).values():
        event, found = "", {}
        for number, _, v in fields(meta):
            if number == 2:
                event = str(v, "utf-8", "replace")
            elif number == 5:
                k, value = stat_value(v, stat_names)
                if k in KEEP:
                    found[k] = value
        out[event] = found
    return name, out


def load(path):
    """File (``.xplane.pb``, or gzipped ``.xplane.pb.gz``) ->
    ``{plane name: {event name: {stat: value}}}``."""
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        space = memoryview(f.read())
    return dict(plane_meta(v)
                for number, _, v in fields(space) if number == 1)

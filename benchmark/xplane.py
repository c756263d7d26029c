"""A reader of the profiler's ``.xplane.pb`` that needs nothing but Python.

``jax.profiler.ProfileData`` gives events with their own stats, not the stats
of their metadata, and on a TPU those are where XLA puts an operation's
category and its framework name (the ``jax.named_scope`` path). So this decodes
the protobuf wire format of ``XSpace`` directly (tsl/profiler/protobuf/
xplane.proto), only the fields the reduction reads.
"""

import collections

Event = collections.namedtuple("Event", "name start_ps duration_ps stats")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")


def _varint(buf, pos):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message; length-delimited
    values come as memoryviews."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 1:
            value, pos = bytes(buf[pos:pos + 8]), pos + 8
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 5:
            value, pos = bytes(buf[pos:pos + 4]), pos + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """One ``XStat`` as ``(name, value)``; a ``ref_value`` resolves to its text."""
    import struct

    name = value = None
    for field, _, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v, str(v))
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field in (3, 4):
            value = v
        elif field in (5, 6):
            value = _text(v)
        elif field == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key = value = None
    for field, _, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _plane(buf):
    name, lines, event_meta, stat_meta = "", [], {}, {}
    for field, _, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            lines.append(v)
        elif field == 4:
            k, m = _map_entry(v)
            event_meta[k] = m
        elif field == 5:
            k, m = _map_entry(v)
            stat_meta[k] = m
    stat_names = {}
    for k, m in stat_meta.items():
        for field, _, v in _fields(m):
            if field == 2:
                stat_names[k] = _text(v)
    metas = {}
    for k, m in event_meta.items():
        mname, display, stats = "", "", {}
        for field, _, v in _fields(m):
            if field == 2:
                mname = _text(v)
            elif field == 4:
                display = _text(v)
            elif field == 5:
                s, val = _stat(v, stat_names)
                stats[s] = val
        metas[k] = (mname or display, stats)
    out = []
    for lbuf in lines:
        lname, t0_ns, events = "", 0, []
        for field, _, v in _fields(lbuf):
            if field == 2:
                lname = _text(v)
            elif field == 3:
                t0_ns = v
            elif field == 4:
                events.append(v)
        decoded = []
        for ebuf in events:
            meta_id = offset = duration = 0
            own = {}
            for field, _, v in _fields(ebuf):
                if field == 1:
                    meta_id = v
                elif field == 2:
                    offset = v
                elif field == 3:
                    duration = v
                elif field == 4:
                    s, val = _stat(v, stat_names)
                    own[s] = val
            mname, mstats = metas.get(meta_id, (str(meta_id), {}))
            decoded.append(Event(mname, t0_ns * 1000 + offset, duration, {**mstats, **own}))
        out.append(Line(lname, decoded))
    return Plane(name, out)


def read(path):
    """The planes of an ``.xplane.pb`` file: ``[Plane(name, [Line(name, [Event])])]``,
    times in picoseconds on one clock, each event's stats merged with its metadata's."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v) for field, _, v in _fields(buf) if field == 1]

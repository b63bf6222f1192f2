"""The ``.xplane.pb`` of a profiler run, read from its wire format.

``jax.profiler.ProfileData`` (what ``trace_reduce.load_xplane`` reads
with) gives an event's own stats, not those of its METADATA, and that is
where the profiler keeps what an HLO operation was made from: ``tf_op``,
the operation's ``op_name`` with every ``jax.named_scope`` around it
(``jit(run)/while/body/.../widedeep.towers/jvp()/dot_general:``; read off
the first trace of ``widedeep_criteo.fit`` by hand, chip run, PR 29).  So
this module decodes the protobuf itself, the few messages of
``tsl/profiler/protobuf/xplane.proto`` it needs and nothing else, into
the plain data ``trace_reduce`` defines:

    {"planes": [{"name": ..., "lines": [{"name": ..., "events":
        [[name, start_ns, duration_ns, {stat: value}], ...]}]}]}

with an event's stats laid over its metadata's.  ``name`` is the
metadata's display name where it has one (``fusion.191``), else its name.
"""

from __future__ import annotations

import struct

# field numbers of xplane.proto
_SPACE_PLANES = 1
_PLANE = {"name": 2, "lines": 3, "event_metadata": 4, "stat_metadata": 5}
_LINE = {"name": 2, "timestamp_ns": 3, "events": 4}
_EVENT = {"metadata_id": 1, "offset_ps": 2, "duration_ps": 3, "stats": 4}
_EVENT_META = {"name": 2, "display_name": 4, "stats": 5}
_STAT_META_NAME = 2


def _varint(buf, at: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message; a length-delimited value
    comes as a view of its bytes, a varint as an int."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value = buf[at:at + size]
            at += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[at:at + size]
            at += size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple:
    key = value = None
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stats(views, stat_names: dict) -> dict:
    out = {}
    for view in views:
        name = value = None
        for number, v in _fields(view):
            if number == 1:
                name = stat_names.get(v)
            elif number == 2:
                value = struct.unpack("<d", v)[0]
            elif number == 3:
                value = v
            elif number == 4:
                value = v - (1 << 64) if v >= 1 << 63 else v
            elif number == 5:
                value = _text(v)
            elif number == 7:
                value = stat_names.get(v)
        if name is not None and value is not None:
            out[name] = value
    return out


def _plane(buf, keep_event) -> dict:
    name, lines, stat_names, event_meta = "", [], {}, {}
    for number, v in _fields(buf):
        if number == _PLANE["name"]:
            name = _text(v)
        elif number == _PLANE["lines"]:
            lines.append(v)
        elif number == _PLANE["stat_metadata"]:
            key, meta = _map_entry(v)
            for n, text in _fields(meta):
                if n == _STAT_META_NAME:
                    stat_names[key] = _text(text)
        elif number == _PLANE["event_metadata"]:
            key, meta = _map_entry(v)
            event_meta[key] = meta
    decoded = {}

    def metadata(key) -> tuple:
        if key not in decoded:
            long_name = display = ""
            stats = []
            for n, v in _fields(event_meta.get(key, b"")):
                if n == _EVENT_META["name"]:
                    long_name = _text(v)
                elif n == _EVENT_META["display_name"]:
                    display = _text(v)
                elif n == _EVENT_META["stats"]:
                    stats.append(v)
            decoded[key] = (display or long_name, _stats(stats, stat_names))
        return decoded[key]

    out_lines = []
    for line in lines:
        line_name, origin_ns, events = "", 0, []
        for number, v in _fields(line):
            if number == _LINE["name"]:
                line_name = _text(v)
            elif number == _LINE["timestamp_ns"]:
                origin_ns = v
            elif number == _LINE["events"]:
                events.append(v)
        out = []
        for event in events:
            key = offset_ps = duration_ps = 0
            own = []
            for number, v in _fields(event):
                if number == _EVENT["metadata_id"]:
                    key = v
                elif number == _EVENT["offset_ps"]:
                    offset_ps = v
                elif number == _EVENT["duration_ps"]:
                    duration_ps = v
                elif number == _EVENT["stats"]:
                    own.append(v)
            event_name, meta_stats = metadata(key)
            if keep_event(name, line_name, event_name):
                out.append([event_name, origin_ns + offset_ps / 1e3,
                            duration_ps / 1e3,
                            {**meta_stats, **_stats(own, stat_names)}])
        if out:
            out_lines.append({"name": line_name, "events": out})
    return {"name": name, "lines": out_lines}


def read(path: str, keep_event=lambda plane, line, event: True) -> dict:
    """The trace at ``path`` as plain data; ``keep_event(plane name, line
    name, event name)`` says which events to keep (a traced window holds
    a million host events no reader wants)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return {"planes": [_plane(v, keep_event) for number, v in _fields(buf)
                       if number == _SPACE_PLANES]}

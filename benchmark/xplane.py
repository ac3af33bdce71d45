"""A profile (``*.xplane.pb``) read with every event's stats, those of its
metadata included. Standard library only.

``jax.profiler.ProfileData`` gives an event's name, start, duration and
its OWN stats. On a TPU the stats that say what an operation is lie one
level up, on the event's metadata (``XEventMetadata.stats``), shared by
every run of that HLO instruction: ``tf_op`` (the ``jax.named_scope`` path:
``jit(serve_step_tc16)/layers/while/body/closed_call/attn/pallas/
_rpa_kernel/pallas_call:``), ``hlo_category``, ``flops``, ``bytes_accessed``,
``source``. ``ProfileData`` does not surface them (read on the first real
trace of PR 25), so this file decodes the protocol buffer's wire format
itself, for the few messages of ``xplane.proto`` (tsl/profiler/protobuf):

  XSpace{planes=1}  XPlane{name=2, lines=3, event_metadata=4, stat_metadata=5}
  XLine{name=2, timestamp_ns=3, events=4}
  XEvent{metadata_id=1, offset_ps=2, duration_ps=3, stats=4}
  XStat{metadata_id=1, double=2, uint64=3, int64=4, str=5, bytes=6, ref=7}
  XEventMetadata{id=1, name=2, stats=5}  XStatMetadata{id=1, name=2}

An event's stats are its metadata's with its own laid over them; a ``ref``
stat is the name of the stat metadata it points to. Times are in ns on the
profiler's clock, as ``ProfileData`` gives them (tested equal to it).
"""
from __future__ import annotations

import struct


def _varint(buf, i: int):
    """``(value, next index)`` of the varint at ``buf[i]``."""
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: memoryview):
    """``(field number, wire type, value)`` of each field of one message:
    an int for a varint, bytes for fixed 64/32, a memoryview for a
    length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = bytes(buf[i:i + 8])
            i += 8
        elif wire == 5:
            value = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wire} of field {number}: not a "
                             "profile this reader knows")
        yield number, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: dict):
    """``(name, value)`` of one XStat."""
    name, value = None, None
    for number, _, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = str(v, "utf-8", "replace")
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    """``(key, value bytes)`` of one entry of a ``map<int64, message>``."""
    key, value = 0, memoryview(b"")
    for number, _, v in _fields(buf):
        if number == 1:
            key = _signed(v)
        elif number == 2:
            value = v
    return key, value


def _plane(buf) -> tuple:
    """``(name, {line name: [(event name, start_ns, duration_ns, stats)]})``
    of one XPlane."""
    name, lines, event_md, stat_md = "", [], [], []
    for number, _, v in _fields(buf):
        if number == 2:
            name = str(v, "utf-8")
        elif number == 3:
            lines.append(v)
        elif number == 4:
            event_md.append(v)
        elif number == 5:
            stat_md.append(v)
    stat_names = {}
    for entry in stat_md:
        key, body = _map_entry(entry)
        for number, _, v in _fields(body):
            if number == 2:
                stat_names[key] = str(v, "utf-8")
    metadata = {}                       # id -> (name, stats)
    for entry in event_md:
        key, body = _map_entry(entry)
        md_name, stats = "", {}
        for number, _, v in _fields(body):
            if number == 2:
                md_name = str(v, "utf-8", "replace")
            elif number == 5:
                k, val = _stat(v, stat_names)
                stats[k] = val
        metadata[key] = (md_name, stats)
    out = {}
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for number, _, v in _fields(line):
            if number == 2:
                line_name = str(v, "utf-8")
            elif number == 3:
                t0_ns = _signed(v)
            elif number == 4:
                events.append(v)
        rows = []
        for ev in events:
            md_id = offset_ps = duration_ps = 0
            own = []
            for number, _, v in _fields(ev):
                if number == 1:
                    md_id = _signed(v)
                elif number == 2:
                    offset_ps = _signed(v)
                elif number == 3:
                    duration_ps = _signed(v)
                elif number == 4:
                    own.append(v)
            ev_name, stats = metadata.get(md_id, ("", {}))
            if own:
                stats = dict(stats)
                for s in own:
                    k, val = _stat(s, stat_names)
                    stats[k] = val
            rows.append((ev_name, t0_ns + offset_ps / 1e3,
                         duration_ps / 1e3, stats))
        out.setdefault(line_name, []).extend(rows)
    return name, out


def read(path: str) -> dict:
    """``{plane: {line: [(event name, start_ns, duration_ns, stats)]}}`` of
    the profile in the file ``path``. Events of one instruction share their
    metadata's stats dict where they have none of their own: read it, do
    not write to it."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for number, wire, v in _fields(space):
        if number == 1 and wire == 2:
            name, lines = _plane(v)
            planes[name] = lines
    return planes

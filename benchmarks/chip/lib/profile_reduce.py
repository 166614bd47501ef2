"""From a turn's `profile.zip` (the JAX profiler's `.xplane.pb`) to device
time: the union of the intervals in which an operation ran on each device,
and the seconds of each operation by the name the trace gives it.

Reads the protobuf's wire format directly (XSpace > XPlane > XLine > XEvent,
tensorflow/tsl/profiler/protobuf/xplane.proto), so the benchmark's runner
needs neither jax nor tensorflow. A trace in which no device plane is found
is an error, never "all idle" and never "all busy".
"""

from __future__ import annotations

import io
import zipfile

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


class NoDevicePlane(Exception):
    pass


def _varint(buf: bytes, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview-free bytes slice."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos : pos + size]
            pos += size
        elif wire == 1:
            value = buf[pos : pos + 8]
            pos += 8
        elif wire == 5:
            value = buf[pos : pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield number, wire, value


def _plane(buf: bytes) -> dict | None:
    """A device plane's op events; None for any other plane (its lines are
    not decoded: the host plane of a traced turn holds tens of thousands)."""
    name, lines, metadata = "", [], {}
    for number, _, value in _fields(buf):
        if number == 2:
            name = value.decode("utf-8", "replace")
            if not name.startswith(DEVICE_PLANE_PREFIX):
                return None
        elif number == 3:
            lines.append(value)
        elif number == 4:
            key = meta_name = None
            for n, _, v in _fields(value):
                if n == 1:
                    key = v
                elif n == 2:
                    for mn, _, mv in _fields(v):
                        if mn == 2:
                            meta_name = mv.decode("utf-8", "replace")
            metadata[key] = meta_name or ""
    if not name.startswith(DEVICE_PLANE_PREFIX):
        return None
    events = []  # (start_ps, end_ps, op name)
    for raw in lines:
        line_name, t0_ns, raw_events = "", 0, []
        for number, _, value in _fields(raw):
            if number == 2:
                line_name = value.decode("utf-8", "replace")
            elif number == 3:
                t0_ns = value
            elif number == 4:
                raw_events.append(value)
        if line_name != OPS_LINE:
            continue
        for raw_event in raw_events:
            meta = offset = duration = 0
            for number, _, value in _fields(raw_event):
                if number == 1:
                    meta = value
                elif number == 2:
                    offset = value
                elif number == 3:
                    duration = value
            start = t0_ns * 1000 + offset
            events.append((start, start + duration, metadata.get(meta, str(meta))))
    return {"name": name, "events": events}


def union_seconds(intervals) -> float:
    """Length of the union of (start_ps, end_ps) intervals, in seconds."""
    total = 0
    end = None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            total += stop - start
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total / 1e12


def reduce_xspace(data: bytes) -> dict:
    """{"busy_s": mean over device planes of the union of their op
    intervals, "ops": {name: seconds, summed over planes / planes},
    "devices": n}."""
    planes = []
    for number, _, value in _fields(data):
        if number == 1 and (plane := _plane(value)) is not None:
            planes.append(plane)
    if not planes:
        raise NoDevicePlane("the trace holds no /device:TPU:* plane")
    ops: dict[str, float] = {}
    busy = 0.0
    for plane in planes:
        busy += union_seconds((s, e) for s, e, _ in plane["events"])
        for start, stop, name in plane["events"]:
            ops[name] = ops.get(name, 0.0) + (stop - start) / 1e12
    n = len(planes)
    return {"busy_s": busy / n, "ops": {k: v / n for k, v in ops.items()}, "devices": n}


def reduce_profile_zip(data: bytes) -> dict:
    with zipfile.ZipFile(io.BytesIO(data)) as archive:
        names = [n for n in archive.namelist() if n.endswith(".xplane.pb")]
        if not names:
            raise NoDevicePlane("profile.zip holds no .xplane.pb")
        return reduce_xspace(archive.read(names[0]))

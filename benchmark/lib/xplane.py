"""What ``jax.profiler.ProfileData`` does not hand out: the stats of an
event's *metadata*. The TPU runtime writes an operation's name stack
(``jit(_als_iteration_body)/als.user_side/als.w8/while/body/closed_call/
als.gather/gather``: the program's ``jax.named_scope``s, PR 24) once per
operation, as the ``tf_op`` stat of its ``XEventMetadata``; an event
holds only its times. This module reads the ``.xplane.pb`` wire format
(protocol buffers: varints and length-prefixed fields) with the standard
library alone and returns ``lib/trace.load``'s plain lists with each
device operation's name stack beside them. Times are seconds on the same
clock as ``ProfileData``'s (``line.timestamp_ns`` + the event's offset).

Field numbers, from tsl/profiler/protobuf/xplane.proto:
XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4
.stat_metadata=5 (maps: key=1, value=2); XLine.name=2 .timestamp_ns=3
.events=4; XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3;
XEventMetadata.id=1 .name=2 .stats=5; XStatMetadata.id=1 .name=2;
XStat.metadata_id=1 .str_value=5 .ref_value=7.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

from .trace import DEVICE_PLANE, OP_LINE

#: The metadata stat that holds an operation's name stack.
NAME_STACK_STAT = "tf_op"

OpEvent = Tuple[str, float, float, str]  # name, start_s, duration_s, name stack


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int) -> Iterator[Tuple[int, int, int]]:
    """(field number, a, b) of one message: for a varint field ``a`` is
    its value and ``b`` is -1; for a length-prefixed field the bytes are
    ``buf[a:b]``. Fixed-width fields are skipped."""
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield number, value, -1
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield number, pos, pos + size
            pos += size
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(buf: bytes, a: int, b: int) -> str:
    return buf[a:b].decode("utf-8", "replace")


def _map_entry(buf: bytes, a: int, b: int) -> Tuple[int, int]:
    """The value message's (start, end) of one map entry."""
    for number, x, y in _fields(buf, a, b):
        if number == 2:
            return x, y
    return a, a


def _plane(buf: bytes, a: int, b: int) -> Dict:
    name, lines, events, stats = "", [], [], []
    for number, x, y in _fields(buf, a, b):
        if number == 2:
            name = _text(buf, x, y)
        elif number == 3:
            lines.append((x, y))
        elif number == 4:
            events.append(_map_entry(buf, x, y))
        elif number == 5:
            stats.append(_map_entry(buf, x, y))
    return {"name": name, "lines": lines, "events": events, "stats": stats}


def _stat_names(buf: bytes, entries) -> Dict[int, str]:
    names = {}
    for a, b in entries:
        ident, name = 0, ""
        for number, x, y in _fields(buf, a, b):
            if number == 1:
                ident = x
            elif number == 2:
                name = _text(buf, x, y)
        names[ident] = name
    return names


def _event_metadata(buf: bytes, entries, stat_names) -> Dict[int, Tuple[str, str]]:
    """metadata id -> (event name, name stack or "")."""
    wanted = {i for i, n in stat_names.items() if n == NAME_STACK_STAT}
    out = {}
    for a, b in entries:
        ident, name, stack = 0, "", ""
        for number, x, y in _fields(buf, a, b):
            if number == 1:
                ident = x
            elif number == 2:
                name = _text(buf, x, y)
            elif number == 5:
                stat_id, value = 0, ""
                for n2, p, q in _fields(buf, x, y):
                    if n2 == 1:
                        stat_id = p
                    elif n2 == 5:
                        value = _text(buf, p, q)
                    elif n2 == 7:  # a reference into the stat names
                        value = stat_names.get(p, "")
                if stat_id in wanted:
                    stack = value
        out[ident] = (name, stack)
    return out


def _line(buf: bytes, a: int, b: int, metadata) -> Tuple[str, List[OpEvent]]:
    name, t0_ns, raw = "", 0, []
    for number, x, y in _fields(buf, a, b):
        if number == 2:
            name = _text(buf, x, y)
        elif number == 3:
            t0_ns = x
        elif number == 4:
            raw.append((x, y))
    events = []
    for x, y in raw:
        ident = offset_ps = duration_ps = 0
        for number, p, _ in _fields(buf, x, y):
            if number == 1:
                ident = p
            elif number == 2:
                offset_ps = p
            elif number == 3:
                duration_ps = p
        op, stack = metadata.get(ident, ("", ""))
        events.append((op, (t0_ns + offset_ps / 1000.0) * 1e-9, duration_ps * 1e-12, stack))
    return name, events


def load(path: str, keep_host: Tuple[str, ...] = ("bench.", "pio.")) -> Dict:
    """``lib/trace.load``'s lists (``devices``: plane -> line -> events
    as (name, start_s, duration_s); ``host``: the host events whose name
    starts with one of ``keep_host``, in order) and beside them
    ``stacks``: plane -> the name stack of each event of that plane's
    ``XLA Ops`` line, in the line's order ("" where the runtime wrote
    none)."""
    with open(path, "rb") as f:
        buf = f.read()
    devices: Dict[str, Dict[str, List]] = {}
    stacks: Dict[str, List[str]] = {}
    host: List = []
    for number, a, b in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        plane = _plane(buf, a, b)
        metadata = _event_metadata(
            buf, plane["events"], _stat_names(buf, plane["stats"]))
        if DEVICE_PLANE.match(plane["name"]):
            lines = devices.setdefault(plane["name"], {})
            for x, y in plane["lines"]:
                name, events = _line(buf, x, y, metadata)
                lines[name] = [ev[:3] for ev in events]
                if name == OP_LINE:
                    stacks[plane["name"]] = [ev[3] for ev in events]
        else:
            kept = {i: m for i, m in metadata.items() if m[0].startswith(keep_host)}
            if kept:
                for x, y in plane["lines"]:
                    host.extend(ev[:3] for ev in _line(buf, x, y, kept)[1] if ev[0])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host, "stacks": stacks}


def scopes_of(stack: str, prefix: str) -> List[str]:
    """The components of a name stack that start with ``prefix``:
    ``jit(f)/als.user_side/als.w8/while/body/als.gather/gather`` ->
    ``["als.user_side", "als.w8", "als.gather"]``."""
    return [part for part in stack.split("/") if part.startswith(prefix)]

"""Just enough of two protobufs of the profiler to read what
`jax.profiler.ProfileData` leaves out.

The TPU profile keeps an operation's HLO `op_name` (stat `tf_op`) and its
`program_id` on the operation's event metadata, shared by all its runs,
and each compiled module as an `xla.HloProto` (stat `Hlo Proto`) on the
`/host:metadata` plane; `ProfileData` exposes only the events' own stats.
This reads the wire format itself, with the field numbers of the
profiler's `xplane.proto` (tsl/profiler/protobuf) and of XLA's `hlo.proto`.
Times come out in ns as `ProfileData` gives them: the line's start plus the
event's offset.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, NamedTuple, Tuple, Union

Stat = Union[str, bytes, int]
_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one message: an int for a
    varint or fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _LEN:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == _I64:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == _I32:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")
        yield field, value


def ints(values) -> List[int]:
    """A repeated integer field, packed (one memoryview) or not."""
    out: List[int] = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


def text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


class Event(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    stats: Dict[str, Stat]   # the stats of the event's metadata


class Plane(NamedTuple):
    name: str
    lines: Dict[str, List[Event]]
    metadata: Dict[str, Dict[str, Stat]]   # event metadata: name -> stats


def _plane(buf) -> Plane:
    name, lines, raw_md, stat_names = "", [], {}, {}
    for field, value in fields(buf):
        if field == 2:
            name = text(value)
        elif field == 3:
            lines.append(value)
        elif field in (4, 5):    # map entry: key 1, value 2
            entry = dict(fields(value))
            md = list(fields(entry.get(2, b"")))
            md_name = next((text(v) for f, v in md if f == 2), "")
            if field == 4:       # XEventMetadata: name 2, stats 5
                raw_md[entry.get(1, 0)] = (
                    md_name, [dict(fields(v)) for f, v in md if f == 5])
            else:                # XStatMetadata: name 2
                stat_names[entry.get(1, 0)] = md_name

    def stats(raw: list) -> Dict[str, Stat]:
        # XStat: metadata 1, uint64 3, int64 4, str 5, bytes 6, ref 7 (the
        # name of another stat)
        out: Dict[str, Stat] = {}
        for stat in raw:
            key = stat_names.get(stat.get(1), "")
            if 5 in stat:
                out[key] = text(stat[5])
            elif 6 in stat:
                out[key] = bytes(stat[6])
            elif 7 in stat:
                out[key] = stat_names.get(stat[7], "")
            elif 3 in stat or 4 in stat:
                out[key] = stat.get(3, stat.get(4))
        return out

    metadata = {mid: (md_name, stats(raw))
                for mid, (md_name, raw) in raw_md.items()}
    out: Dict[str, List[Event]] = {}
    for raw in lines:
        line_name, t0, events = "", 0, []
        for field, value in fields(raw):   # XLine: name 2, start 3, events 4
            if field == 2:
                line_name = text(value)
            elif field == 3:
                t0 = value
            elif field == 4:
                events.append(value)
        evs = out.setdefault(line_name, [])
        for raw_ev in events:     # XEvent: metadata 1, offset 2, duration 3
            ev = dict(fields(raw_ev))
            md_name, md_stats = metadata.get(ev.get(1, 0), ("", {}))
            evs.append(Event(md_name, t0 + ev.get(2, 0) // 1000,
                             ev.get(3, 0) // 1000, md_stats))
    return Plane(name, out, dict(metadata.values()))


def read(path: str) -> List[Plane]:
    """Every plane of an `.xplane.pb` file."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(value) for field, value in fields(buf) if field == 1]


class Instruction(NamedTuple):
    name: str
    op_name: str
    operands: List[int]       # instruction ids
    calls: List[int]          # computation ids


def hlo_module(hlo_proto: bytes) -> Tuple[Dict[int, Instruction],
                                          Dict[int, List[int]]]:
    """The instructions of an `xla.HloProto`'s module by id, and the
    instruction ids of each of its computations by computation id."""
    module = next((v for f, v in fields(memoryview(hlo_proto)) if f == 1),
                  b"")
    instructions: Dict[int, Instruction] = {}
    computations: Dict[int, List[int]] = {}
    for f, comp in fields(module):
        if f != 3:               # HloModuleProto.computations
            continue
        comp_id, ids = 0, []
        for g, v in fields(comp):    # HloComputationProto: instructions 2, id 5
            if g == 5:
                comp_id = v
            if g != 2:
                continue
            # HloInstructionProto: name 1, metadata 7 (OpMetadata.op_name
            # 2), id 35, operand_ids 36, called_computation_ids 38
            ins = list(fields(v))
            one = dict(ins)
            meta = dict(fields(one.get(7, b"")))
            instructions[one.get(35, 0)] = Instruction(
                text(one.get(1, b"")), text(meta.get(2, b"")),
                ints(x for h, x in ins if h == 36),
                ints(x for h, x in ins if h == 38))
            ids.append(one.get(35, 0))
        computations[comp_id] = ids
    return instructions, computations

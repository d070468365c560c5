"""Read and write profiler traces (``.xplane.pb``) with every stat kept.

``jax.profiler.ProfileData`` gives an event's own stats but not those of its
metadata, which is where a TPU trace keeps what an operation shares across
its runs (its scope path among them). This reads the file with protobuf
alone, from the schema of ``tsl/profiler/protobuf/xplane.proto`` written out
below, and gives each event its own stats and its metadata's, with
``ref_value`` strings resolved. It also writes traces built by hand, for
the tests.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_MESSAGES = {
    "XSpace": [("planes", 1, "XPlane", True), ("errors", 2, _F.TYPE_STRING, True),
               ("warnings", 3, _F.TYPE_STRING, True), ("hostnames", 4, _F.TYPE_STRING, True)],
    "XPlane": [("id", 1, _F.TYPE_INT64, False), ("name", 2, _F.TYPE_STRING, False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "XPlane.EventMetadataEntry", True),
               ("stat_metadata", 5, "XPlane.StatMetadataEntry", True),
               ("stats", 6, "XStat", True)],
    "XLine": [("id", 1, _F.TYPE_INT64, False), ("display_id", 10, _F.TYPE_INT64, False),
              ("name", 2, _F.TYPE_STRING, False), ("display_name", 11, _F.TYPE_STRING, False),
              ("timestamp_ns", 3, _F.TYPE_INT64, False), ("duration_ps", 9, _F.TYPE_INT64, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _F.TYPE_INT64, False), ("offset_ps", 2, _F.TYPE_INT64, False),
               ("num_occurrences", 5, _F.TYPE_INT64, False),
               ("duration_ps", 3, _F.TYPE_INT64, False), ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, _F.TYPE_INT64, False), ("double_value", 2, _F.TYPE_DOUBLE, False),
              ("uint64_value", 3, _F.TYPE_UINT64, False), ("int64_value", 4, _F.TYPE_INT64, False),
              ("str_value", 5, _F.TYPE_STRING, False), ("bytes_value", 6, _F.TYPE_BYTES, False),
              ("ref_value", 7, _F.TYPE_UINT64, False)],
    "XEventMetadata": [("id", 1, _F.TYPE_INT64, False), ("name", 2, _F.TYPE_STRING, False),
                       ("display_name", 4, _F.TYPE_STRING, False),
                       ("metadata", 3, _F.TYPE_BYTES, False), ("stats", 5, "XStat", True),
                       ("child_id", 6, _F.TYPE_INT64, True)],
    "XStatMetadata": [("id", 1, _F.TYPE_INT64, False), ("name", 2, _F.TYPE_STRING, False),
                      ("description", 3, _F.TYPE_STRING, False)],
}
_ONEOFS = {"XEvent": ("data", ("offset_ps", "num_occurrences")),
           "XStat": ("value", ("double_value", "uint64_value", "int64_value", "str_value",
                               "bytes_value", "ref_value"))}
_PKG = "chipbench.xplane"


def _field(msg, name, number, kind, repeated):
    f = msg.field.add(name=name, number=number,
                      label=_F.LABEL_REPEATED if repeated else _F.LABEL_OPTIONAL)
    if isinstance(kind, str):
        f.type, f.type_name = _F.TYPE_MESSAGE, f".{_PKG}.{kind}"
    else:
        f.type = kind
    return f


def _classes():
    fd = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto", package=_PKG,
                                            syntax="proto3")
    for name, fields in _MESSAGES.items():
        msg = fd.message_type.add(name=name)
        oneof = _ONEOFS.get(name)
        if oneof:
            msg.oneof_decl.add(name=oneof[0])
        for fname, number, kind, repeated in fields:
            f = _field(msg, fname, number, kind, repeated)
            if oneof and fname in oneof[1]:
                f.oneof_index = 0
        if name == "XPlane":
            for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                                 ("StatMetadataEntry", "XStatMetadata")):
                e = msg.nested_type.add(name=entry)
                e.options.map_entry = True
                _field(e, "key", 1, _F.TYPE_INT64, False)
                _field(e, "value", 2, value, False)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {n: message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{_PKG}.{n}"))
            for n in _MESSAGES}


_CLS = _classes()
XSpace = _CLS["XSpace"]


def load(path: str):
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _value(stat, names: Dict[int, str]):
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return names.get(stat.ref_value, "")
    return getattr(stat, kind) if kind else None


def events(plane) -> Iterator[Tuple[int, str, str, float, float, Dict[str, object]]]:
    """(line index, line name, event name, start ns, duration ns, stats) of
    every event of ``plane``; the stats are the event's metadata's, then its
    own."""
    names = {k: m.name for k, m in plane.stat_metadata.items()}
    meta = {}
    for k, m in plane.event_metadata.items():
        meta[k] = (m.display_name or m.name, {names.get(s.metadata_id, ""): _value(s, names)
                                             for s in m.stats})
    for k, line in enumerate(plane.lines):
        for ev in line.events:
            name, mstats = meta.get(ev.metadata_id, ("", {}))
            stats = dict(mstats)
            stats.update({names.get(s.metadata_id, ""): _value(s, names) for s in ev.stats})
            yield (k, line.name, name, (line.timestamp_ns * 1000 + ev.offset_ps) / 1000.0,
                   ev.duration_ps / 1000.0, stats)


def build(rows: List[tuple]) -> bytes:
    """A trace of ``rows`` (plane, line, event name, start us, duration us,
    event stats, metadata stats), every line starting at time 0."""
    space = XSpace()
    planes: Dict[str, object] = {}
    for plane_name, line_name, name, start_us, dur_us, stats, mstats in rows:
        plane = planes.get(plane_name)
        if plane is None:
            plane = planes[plane_name] = space.planes.add(id=len(planes) + 1, name=plane_name)
        line = next((ln for ln in plane.lines if ln.name == line_name), None)
        if line is None:
            line = plane.lines.add(id=len(plane.lines) + 1, name=line_name, timestamp_ns=0)
        mid = next((k for k, m in plane.event_metadata.items() if m.name == name), None)
        if mid is None:
            mid = len(plane.event_metadata) + 1
            plane.event_metadata[mid].id = mid
            plane.event_metadata[mid].name = name
            _add_stats(plane, plane.event_metadata[mid].stats, mstats)
        ev = line.events.add(metadata_id=mid, offset_ps=round(start_us * 1e6),
                             duration_ps=round(dur_us * 1e6))
        _add_stats(plane, ev.stats, stats)
    return space.SerializeToString()


def _add_stats(plane, into, stats: Dict[str, object]) -> None:
    for key, v in (stats or {}).items():
        sid = next((k for k, m in plane.stat_metadata.items() if m.name == key), None)
        if sid is None:
            sid = len(plane.stat_metadata) + 1
            plane.stat_metadata[sid].id = sid
            plane.stat_metadata[sid].name = key
        st = into.add(metadata_id=sid)
        if isinstance(v, str):
            st.str_value = v
        elif isinstance(v, float):
            st.double_value = v
        else:
            st.int64_value = int(v)

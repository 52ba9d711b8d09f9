"""Read a profiler's ``.xplane.pb`` (an ``XSpace``) with nothing but protobuf.

``jax.profiler.ProfileData`` does not show the stats of an op's metadata
(its HLO category, the JAX op it came from), which is what sorts device ops
into layers.  The message layout below follows ``xplane.proto`` of the
profiler (tsl/profiler/protobuf/xplane.proto), field numbers included.
"""
from __future__ import annotations

import functools

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
# Only the fields read here; the parser skips the others.
_FIELDS = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "XPlane.EventMetadataEntry", True),
               ("stat_metadata", 5, "XPlane.StatMetadataEntry", True)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False),
               ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("double_value", 2, "double", False),
              ("uint64_value", 3, "uint64", False),
              ("int64_value", 4, "int64", False),
              ("str_value", 5, "string", False),
              ("bytes_value", 6, "bytes", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("display_name", 4, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
}
#: The stat's value is one of these fields (a proto ``oneof``).
_STAT_VALUES = {"double_value", "uint64_value", "int64_value", "str_value",
                "bytes_value", "ref_value"}
_SCALARS = {"int64": _F.TYPE_INT64, "uint64": _F.TYPE_UINT64,
            "double": _F.TYPE_DOUBLE, "string": _F.TYPE_STRING,
            "bytes": _F.TYPE_BYTES}
_PACKAGE = "chipbench_xplane"


def _add_field(msg, name, number, typ, repeated):
    f = msg.field.add(name=name, number=number)
    f.label = _F.LABEL_REPEATED if repeated else _F.LABEL_OPTIONAL
    if typ in _SCALARS:
        f.type = _SCALARS[typ]
    else:
        f.type = _F.TYPE_MESSAGE
        f.type_name = f".{_PACKAGE}.{typ}"
    return f


@functools.cache
def _xspace():
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package=_PACKAGE, syntax="proto3")
    for name, fields in _FIELDS.items():
        msg = fd.message_type.add(name=name)
        if name == "XStat":
            msg.oneof_decl.add(name="value")
        for fname, number, typ, repeated in fields:
            f = _add_field(msg, fname, number, typ, repeated)
            if name == "XStat" and fname in _STAT_VALUES:
                f.oneof_index = 0
        if name == "XPlane":
            for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                                 ("StatMetadataEntry", "XStatMetadata")):
                nested = msg.nested_type.add(name=entry)
                nested.options.map_entry = True
                _add_field(nested, "key", 1, "int64", False)
                _add_field(nested, "value", 2, value, False)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


def read(path) -> object:
    """The ``XSpace`` message in the file at ``path``."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_value(stat, stat_names: dict):
    """A stat's value; a ``ref_value`` names another stat metadata."""
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    value = getattr(stat, kind)
    if kind == "ref_value":
        return stat_names.get(value, value)
    if kind == "bytes_value":
        return None
    return value


def events(plane):
    """(line name, event name, start ns, end ns, stats) of every event of a
    plane, its metadata's stats first and the event's own over them."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    meta = {}
    for k, m in plane.event_metadata.items():
        meta[k] = (m.display_name or m.name,
                   {stat_names.get(s.metadata_id, s.metadata_id):
                    stat_value(s, stat_names) for s in m.stats})
    for line in plane.lines:
        base_ps = line.timestamp_ns * 1000
        for e in line.events:
            name, stats = meta.get(e.metadata_id, ("", {}))
            stats = dict(stats)
            stats.update({stat_names.get(s.metadata_id, s.metadata_id):
                          stat_value(s, stat_names) for s in e.stats})
            start = (base_ps + e.offset_ps) / 1000.0
            yield (line.name, name, start,
                   start + e.duration_ps / 1000.0, stats)

"""Read the profiler's `.xplane.pb` (an XSpace protobuf) without TensorFlow.

The message types are declared here from their field numbers, so the
generic protobuf runtime parses the file. `jax.profiler.ProfileData` gives
events but not their metadata's stats, and the name stack of a device op
(`tf_op`, the program's `jax.named_scope` labels) lives there.
"""
from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_MESSAGES = {
    "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
              ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
              ("str_value", 5, "string"), ("bytes_value", 6, "bytes"),
              ("ref_value", 7, "uint64")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64"), ("stats", 4, "*XStat")],
    "XLine": [("id", 1, "int64"), ("name", 2, "string"),
              ("timestamp_ns", 3, "int64"), ("events", 4, "*XEvent")],
    "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                       ("display_name", 4, "string"),
                       ("stats", 5, "*XStat")],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
    "EventMetadataEntry": [("key", 1, "int64"),
                           ("value", 2, ".XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"),
                          ("value", 2, ".XStatMetadata")],
    "XPlane": [("id", 1, "int64"), ("name", 2, "string"),
               ("lines", 3, "*XLine"),
               ("event_metadata", 4, "*EventMetadataEntry"),
               ("stat_metadata", 5, "*StatMetadataEntry"),
               ("stats", 6, "*XStat")],
    "XSpace": [("planes", 1, "*XPlane")],
}
_SCALARS = {"int64": _F.TYPE_INT64, "uint64": _F.TYPE_UINT64,
            "double": _F.TYPE_DOUBLE, "string": _F.TYPE_STRING,
            "bytes": _F.TYPE_BYTES}
_PACKAGE = "perfbench_xplane"
_CLASSES = {}


def _classes():
    if _CLASSES:
        return _CLASSES
    fd = descriptor_pb2.FileDescriptorProto(name=_PACKAGE + ".proto",
                                            package=_PACKAGE, syntax="proto3")
    for name, fields in _MESSAGES.items():
        msg = fd.message_type.add(name=name)
        for fname, number, kind in fields:
            f = msg.field.add(name=fname, number=number)
            if kind in _SCALARS:
                f.type, f.label = _SCALARS[kind], _F.LABEL_OPTIONAL
            else:
                f.type = _F.TYPE_MESSAGE
                f.label = (_F.LABEL_REPEATED if kind[0] == "*"
                           else _F.LABEL_OPTIONAL)
                f.type_name = f".{_PACKAGE}.{kind.lstrip('*.')}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    for name in _MESSAGES:
        _CLASSES[name] = message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"{_PACKAGE}.{name}"))
    return _CLASSES


def read(path: str):
    """The XSpace in the file at `path`."""
    with open(path, "rb") as f:
        return _classes()["XSpace"].FromString(f.read())


def stat_values(stats, stat_names) -> dict:
    """{stat name: value} of a repeated XStat, interned strings resolved."""
    out = {}
    for s in stats:
        if s.ref_value:
            v = stat_names.get(s.ref_value, "")
        elif s.str_value:
            v = s.str_value
        elif s.int64_value:
            v = s.int64_value
        elif s.uint64_value:
            v = s.uint64_value
        else:
            v = s.double_value
        out[stat_names.get(s.metadata_id, str(s.metadata_id))] = v
    return out

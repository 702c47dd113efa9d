"""The few messages of the profiler's `XSpace` (tsl's
`tsl/profiler/protobuf/xplane.proto`) that the stage reduction reads,
with the field numbers of that file, so that a trace's event metadata
(the stats JAX's `ProfileData` does not expose, such as each device
operation's `tf_op` name stack) can be decoded with `google.protobuf`
alone. Fields left out here are not decoded."""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

PACKAGE = "bench.xspace"

_F = descriptor_pb2.FieldDescriptorProto
_OPT, _REP = _F.LABEL_OPTIONAL, _F.LABEL_REPEATED
_INT64, _UINT64, _DOUBLE = _F.TYPE_INT64, _F.TYPE_UINT64, _F.TYPE_DOUBLE
_STRING, _BYTES, _MSG = _F.TYPE_STRING, _F.TYPE_BYTES, _F.TYPE_MESSAGE

#: message -> [(field, number, label, type, message type)]
MESSAGES = {
    "XSpace": [("planes", 1, _REP, _MSG, "XPlane")],
    "XPlane": [("id", 1, _OPT, _INT64, None),
               ("name", 2, _OPT, _STRING, None),
               ("lines", 3, _REP, _MSG, "XLine"),
               ("event_metadata", 4, _REP, _MSG,
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _REP, _MSG, "XPlane.StatMetadataEntry")],
    "XLine": [("id", 1, _OPT, _INT64, None),
              ("name", 2, _OPT, _STRING, None),
              ("timestamp_ns", 3, _OPT, _INT64, None),
              ("events", 4, _REP, _MSG, "XEvent")],
    "XEvent": [("metadata_id", 1, _OPT, _INT64, None),
               ("offset_ps", 2, _OPT, _INT64, None),
               ("duration_ps", 3, _OPT, _INT64, None),
               ("stats", 4, _REP, _MSG, "XStat")],
    "XStat": [("metadata_id", 1, _OPT, _INT64, None),
              ("double_value", 2, _OPT, _DOUBLE, None),
              ("uint64_value", 3, _OPT, _UINT64, None),
              ("int64_value", 4, _OPT, _INT64, None),
              ("str_value", 5, _OPT, _STRING, None),
              ("bytes_value", 6, _OPT, _BYTES, None),
              ("ref_value", 7, _OPT, _UINT64, None)],
    "XEventMetadata": [("id", 1, _OPT, _INT64, None),
                       ("name", 2, _OPT, _STRING, None),
                       ("stats", 5, _REP, _MSG, "XStat")],
    "XStatMetadata": [("id", 1, _OPT, _INT64, None),
                      ("name", 2, _OPT, _STRING, None)],
}
#: XPlane's two maps, as protobuf encodes a map: a nested entry message
MAPS = {"EventMetadataEntry": "XEventMetadata",
        "StatMetadataEntry": "XStatMetadata"}
#: XStat's value is one of these
STAT_VALUES = ("double_value", "uint64_value", "int64_value", "str_value",
               "bytes_value", "ref_value")


def _fields(msg, fields) -> None:
    for name, number, label, kind, type_name in fields:
        f = msg.field.add(name=name, number=number, label=label, type=kind)
        if type_name:
            f.type_name = f".{PACKAGE}.{type_name}"


def _file() -> descriptor_pb2.FileDescriptorProto:
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench/xspace.proto", package=PACKAGE, syntax="proto3")
    for name, fields in MESSAGES.items():
        msg = fd.message_type.add(name=name)
        _fields(msg, fields)
        if name == "XStat":
            msg.oneof_decl.add(name="value")
            for f in msg.field:
                if f.name in STAT_VALUES:
                    f.oneof_index = 0
        if name == "XPlane":
            for entry, value in MAPS.items():
                sub = msg.nested_type.add(name=entry)
                sub.options.map_entry = True
                _fields(sub, [("key", 1, _OPT, _INT64, None),
                              ("value", 2, _OPT, _MSG, value)])
    return fd


_POOL = descriptor_pool.DescriptorPool()
_POOL.Add(_file())
XSpace = message_factory.GetMessageClass(
    _POOL.FindMessageTypeByName(f"{PACKAGE}.XSpace"))


def parse(data: bytes):
    """The `XSpace` message serialised in `data`."""
    space = XSpace()
    space.ParseFromString(data)
    return space


def stat_value(stat, stat_names):
    """A stat's value; a `ref_value` names another stat's metadata."""
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    value = getattr(stat, kind)
    return stat_names.get(value, "") if kind == "ref_value" else value

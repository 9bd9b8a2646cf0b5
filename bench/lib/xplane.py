"""Reader of the profiler's ``.xplane.pb`` with the event metadata.

``jax.profiler.ProfileData`` gives each event's name, times and own
stats, but not the stats of its metadata, where a device op keeps its
``tf_op`` (the op's name path, ``jit(run_hops)/while/body/pipeann.hop.
merge/...``). This module declares the part of the XSpace schema
(``tsl/profiler/protobuf/xplane.proto``) that the reduction reads, with
the field numbers of that file, and parses with the installed protobuf
runtime; fields it does not declare are skipped.
"""
from __future__ import annotations

import functools

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_OPT, _REP = _F.LABEL_OPTIONAL, _F.LABEL_REPEATED

# message -> [(field, number, type, repeated, message type)]
_SCHEMA = {
    "XStat": [("metadata_id", 1, _F.TYPE_INT64, False, None),
              ("str_value", 5, _F.TYPE_STRING, False, None)],
    "XEvent": [("metadata_id", 1, _F.TYPE_INT64, False, None)],
    "XLine": [("name", 2, _F.TYPE_STRING, False, None),
              ("events", 4, _F.TYPE_MESSAGE, True, "XEvent")],
    "XEventMetadata": [("id", 1, _F.TYPE_INT64, False, None),
                       ("name", 2, _F.TYPE_STRING, False, None),
                       ("stats", 5, _F.TYPE_MESSAGE, True, "XStat")],
    "XStatMetadata": [("id", 1, _F.TYPE_INT64, False, None),
                      ("name", 2, _F.TYPE_STRING, False, None)],
    "XPlane": [("name", 2, _F.TYPE_STRING, False, None),
               ("lines", 3, _F.TYPE_MESSAGE, True, "XLine"),
               ("event_metadata", 4, _F.TYPE_MESSAGE, True,
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _F.TYPE_MESSAGE, True,
                "XPlane.StatMetadataEntry")],
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, True, "XPlane")],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}
_PACKAGE = "bench_xplane"


def _add_fields(msg, fields) -> None:
    for name, number, typ, repeated, ref in fields:
        f = msg.field.add(name=name, number=number, type=typ,
                          label=_REP if repeated else _OPT)
        if ref:
            f.type_name = f".{_PACKAGE}.{ref}"


@functools.lru_cache(maxsize=1)
def _space_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=_PACKAGE, syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = fd.message_type.add(name=name)
        _add_fields(msg, fields)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                e = msg.nested_type.add(name=entry)
                e.options.map_entry = True
                _add_fields(e, [("key", 1, _F.TYPE_INT64, False, None),
                                ("value", 2, _F.TYPE_MESSAGE, False, value)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


def read(path):
    """The XSpace message of ``path``."""
    with open(path, "rb") as f:
        return _space_class().FromString(f.read())

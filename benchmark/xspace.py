"""A reader of the XSpace fields that ``jax.profiler.ProfileData`` does
not expose: each event's metadata id, and the stats of its event
metadata. A device operation's metadata carries its scope path as the
``tf_op`` stat (``jit(f)/while/body/<named scope>/.../<op>``), which
stays the same when XLA renumbers its instructions.

The schema below is the subset of the profiler's ``xplane.proto`` read
here, declared in code so that nothing but ``protobuf`` is imported.
Field numbers are the proto's; its two ``map`` fields are declared as
their wire form, repeated key/value entries. Fields left out are
skipped by the parser.
"""

from __future__ import annotations

import gzip

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_INT64, _UINT64 = _F.TYPE_INT64, _F.TYPE_UINT64
_STRING, _MESSAGE = _F.TYPE_STRING, _F.TYPE_MESSAGE

# message -> [(field, number, type, message type or None)]
_SCHEMA = {
    "XStat": [("metadata_id", 1, _INT64, None),
              ("str_value", 5, _STRING, None),
              ("ref_value", 7, _UINT64, None)],
    "XEvent": [("metadata_id", 1, _INT64, None),
               ("offset_ps", 2, _INT64, None),
               ("duration_ps", 3, _INT64, None)],
    "XLine": [("name", 2, _STRING, None),
              ("timestamp_ns", 3, _INT64, None),
              ("events", 4, _MESSAGE, "XEvent")],
    "XEventMetadata": [("name", 2, _STRING, None),
                       ("stats", 5, _MESSAGE, "XStat")],
    "XStatMetadata": [("name", 2, _STRING, None)],
    "EventMetadataEntry": [("key", 1, _INT64, None),
                           ("value", 2, _MESSAGE, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _INT64, None),
                          ("value", 2, _MESSAGE, "XStatMetadata")],
    "XPlane": [("name", 2, _STRING, None),
               ("lines", 3, _MESSAGE, "XLine"),
               ("event_metadata", 4, _MESSAGE, "EventMetadataEntry"),
               ("stat_metadata", 5, _MESSAGE, "StatMetadataEntry")],
    "XSpace": [("planes", 1, _MESSAGE, "XPlane")],
}
_PACKAGE = "benchmark.xspace"
# a message field that holds one value rather than a list
_SINGULAR = {("EventMetadataEntry", "value"), ("StatMetadataEntry", "value")}


def _space_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xspace.proto", package=_PACKAGE, syntax="proto3")
    for message, fields in _SCHEMA.items():
        m = fd.message_type.add(name=message)
        for name, number, ftype, mtype in fields:
            f = m.field.add(name=name, number=number, type=ftype)
            if mtype is None or (message, name) in _SINGULAR:
                f.label = _F.LABEL_OPTIONAL
            else:
                f.label = _F.LABEL_REPEATED
            if mtype is not None:
                f.type_name = f".{_PACKAGE}.{mtype}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


XSpace = _space_class()


def read(path: str):
    """The parsed XSpace of one ``.xplane.pb`` or ``.xplane.pb.gz``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return XSpace.FromString(f.read())


def stat_strings(plane, stat_name: str) -> dict:
    """Event metadata id -> the string value of stat ``stat_name`` in
    that metadata, for every event metadata of ``plane`` that has it.
    A string stat is stored inline or, for repeated strings, as a
    reference to a stat metadata whose name is the string."""
    names = {e.key: e.value.name for e in plane.stat_metadata}
    wanted = {k for k, v in names.items() if v == stat_name}
    out = {}
    for entry in plane.event_metadata:
        for stat in entry.value.stats:
            if stat.metadata_id in wanted:
                out[entry.key] = stat.str_value or names.get(
                    stat.ref_value, "")
                break
    return out


def events(plane, line_name: str | None = None):
    """``(metadata id, start ns, end ns)`` of every event of ``plane``
    (of one line when ``line_name`` is given), on the profiler's clock:
    the line's timestamp plus the event's offset, as ``ProfileData``
    places them."""
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        base = float(line.timestamp_ns)
        for ev in line.events:
            start = base + ev.offset_ps * 1e-3
            yield ev.metadata_id, start, start + ev.duration_ps * 1e-3


def event_names(plane) -> dict:
    """Event metadata id -> the metadata's name."""
    return {e.key: e.value.name for e in plane.event_metadata}

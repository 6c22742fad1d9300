"""Device time of the local update's ops under a named scope of the program.

A device op's name in the trace (``device_trace.Event.name``) is the HLO text
that the trace's event metadata holds as ``name``; beside it the metadata
holds a ``tf_op`` stat with the op's name-scope path, such as
``jit(local_update)/while/body/closed_call/transpose(jvp(mla))/dot_general``.
This module decodes the ``.xplane.pb`` with ``google.protobuf`` alone (the
message types are declared here, as ``tsl/profiler/protobuf/xplane.proto``
defines them), maps each op of ``jit_local_update`` to its path, and sums the
device time of the leaf ops whose path holds the scope as a component, with
the transforms' wrappers (``jvp(...)``, ``transpose(...)``) taken off: the
forward, the backward and the rematerialised forward alike.
"""
from __future__ import annotations

import functools
import re
from pathlib import Path
from typing import Dict, Optional

import device_trace

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"
MODULE = "local_update"

_FIELDS = {
    # message: [(field, number, type, repeated, message type)]; the fields
    # read here, as xplane.proto numbers them (protobuf skips the others)
    "XSpace": [("planes", 1, "message", True, "XPlane")],
    "XPlane": [("name", 2, "string", False, None),
               ("event_metadata", 4, "message", True, "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, "message", True, "XPlane.StatMetadataEntry")],
    "XPlane.EventMetadataEntry": [("key", 1, "int64", False, None),
                                  ("value", 2, "message", False, "XEventMetadata")],
    "XPlane.StatMetadataEntry": [("key", 1, "int64", False, None),
                                 ("value", 2, "message", False, "XStatMetadata")],
    "XStat": [("metadata_id", 1, "int64", False, None), ("str_value", 5, "string", False, None)],
    "XEventMetadata": [("name", 2, "string", False, None), ("stats", 5, "message", True, "XStat")],
    "XStatMetadata": [("name", 2, "string", False, None)],
}


@functools.lru_cache(maxsize=1)
def _space_class():
    """The XSpace message class, from the descriptors declared above."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory
    F = descriptor_pb2.FieldDescriptorProto
    kinds = {"int64": F.TYPE_INT64, "string": F.TYPE_STRING, "message": F.TYPE_MESSAGE}
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="benchxplane",
                                             syntax="proto3")
    messages = {}
    for full in _FIELDS:
        outer, _, inner = full.partition(".")
        if inner:
            msg = messages[outer].nested_type.add(name=inner)
            msg.options.map_entry = True
        else:
            msg = fdp.message_type.add(name=full)
        messages[full] = msg
        for name, number, kind, repeated, type_name in _FIELDS[full]:
            f = msg.field.add(name=name, number=number, type=kinds[kind],
                              label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if type_name:
                f.type_name = f".benchxplane.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("benchxplane.XSpace"))


@functools.lru_cache(maxsize=4)
def op_paths(path: str) -> Dict[str, str]:
    """{op name: name-scope path} of the device ops of ``jit_<MODULE>``."""
    space = _space_class()()
    space.ParseFromString(Path(path).read_bytes())
    out: Dict[str, str] = {}
    for plane in space.planes:
        if not device_trace._is_device(plane.name):
            continue
        tf_op = next((k for k, v in plane.stat_metadata.items() if v.name == "tf_op"), None)
        if tf_op is None:
            continue
        for md in plane.event_metadata.values():
            scope = next((s.str_value for s in md.stats if s.metadata_id == tf_op), "")
            if scope.startswith(f"jit({MODULE})"):
                out[md.name] = re.sub(r":[^/]*$", "", scope)     # "<path>:<op type>"
    return out


def latest_trace(trace_dir: Path = TRACE_DIR) -> Optional[Path]:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def in_scope(op_path: str, scope: str) -> bool:
    """Whether a component of the path, unwrapped, is ``scope``."""
    for comp in op_path.split("/"):
        while True:
            m = _WRAPPED.match(comp)
            if not m:
                break
            comp = m.group(1)
        if comp == scope:
            return True
    return False


def scope_ms(ctx, scope: str, trace_path: Optional[Path] = None) -> Optional[float]:
    """Device ms per local update of the ops under ``scope`` in the traced
    window, or None where no op of the window is under it (a program without
    the scope, or no trace)."""
    trace_path = trace_path or latest_trace()
    if trace_path is None:
        return None
    _, n = ctx.module_time((MODULE,))
    if n == 0:
        return None
    paths = op_paths(str(trace_path))
    total = 0.0
    for op in device_trace.leaf_ops(ctx.trace.ops):
        if (ctx.lo <= op.start < ctx.hi and MODULE in op.module
                and in_scope(paths.get(op.name, ""), scope)):
            total += min(op.end, ctx.hi) - op.start
    return total * 1e-6 / n if total > 0 else None

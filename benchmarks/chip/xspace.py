"""The ``tf_op`` name of every device op in a profiler trace.

A JAX profiler trace (``.xplane.pb``) is a serialized ``XSpace``
protocol buffer.  Each plane keeps one ``XEventMetadata`` per distinct
event, and for a device op that metadata carries the op's ``tf_op``
stat: the name stack of the jitted program (``jit(run)/while/body/
closed.to_grid/gather``, one of the two static gathers that fill the
scan grid), which ``jax.named_scope`` extends.
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
this module reads them from the wire format itself, with nothing but the
standard library.  It reads only what it needs::

    XSpace          planes = 1
    XPlane          name = 2, event_metadata = 4 (map), stat_metadata = 5 (map)
    XEventMetadata  name = 2, stats = 5
    XStatMetadata   id = 1, name = 2
    XStat           metadata_id = 1, str_value = 5, ref_value = 7

A map is a repeated message whose ``key`` is field 1 and ``value`` field
2.  A ``ref_value`` names the ``XStatMetadata`` whose ``name`` is the
string, the profiler's way of storing a repeated string once.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple

TF_OP = "tf_op"

_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int = -1
            ) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of a message in ``buf[lo:hi]``: an int
    for a varint or fixed field, a ``(start, end)`` span of ``buf`` for a
    length-delimited one (so that skipping a field copies nothing)."""
    hi = len(buf) if hi < 0 else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == _LEN:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == _I64:
            yield field, int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == _I32:
            yield field, int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i} is not "
                             "supported")


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, span) -> Iterator[Tuple[int, int]]:
    """The value span of one map entry (its key is field 1)."""
    for field, value in _fields(buf, *span):
        if field == 2:
            yield value


def _plane_tf_ops(buf: bytes, lo: int, hi: int) -> Tuple[str, Dict[str, str]]:
    name, events, stat_names = "", [], {}
    for field, value in _fields(buf, lo, hi):
        if field == 2:
            name = _text(buf, value)
        elif field == 4:
            events.extend(_map_values(buf, value))
        elif field == 5:
            for meta in _map_values(buf, value):
                sid, sname = None, ""
                for f, v in _fields(buf, *meta):
                    if f == 1:
                        sid = v
                    elif f == 2:
                        sname = _text(buf, v)
                if sid is not None:
                    stat_names[sid] = sname
    tf_op_ids = {sid for sid, sname in stat_names.items() if sname == TF_OP}
    out: Dict[str, str] = {}
    if not tf_op_ids:
        return name, out
    for meta in events:
        ev_name, op = "", None
        for f, v in _fields(buf, *meta):
            if f == 2:
                ev_name = _text(buf, v)
            elif f == 5:
                sid, text = None, None
                for sf, sv in _fields(buf, *v):
                    if sf == 1:
                        sid = sv
                    elif sf == 5:
                        text = _text(buf, sv)
                    elif sf == 7:
                        text = stat_names.get(sv)
                if sid in tf_op_ids and text is not None:
                    op = text
        if op is not None:
            out[ev_name] = op
    return name, out


def tf_ops(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Per plane name, each event metadata's name (a device op's HLO
    text) mapped to its ``tf_op``; planes without any are left out."""
    out = {}
    for field, value in _fields(xspace):
        if field == 1:
            name, ops = _plane_tf_ops(xspace, *value)
            if ops:
                out[name] = ops
    return out

"""Wire codec: dataclasses <-> Nomad-API-shaped JSON dicts.

The reference's `api/` package defines the public JSON shapes (CamelCase
field names, durations as nanosecond ints).  Rather than hand-writing a
converter per struct, this module derives the wire form from the dataclass
definitions:

  - snake_case -> CamelCase with Nomad's acronym conventions
    (`id`->`ID`, `cpu`->`CPU`, `memory_mb`->`MemoryMB`, ...)
  - fields ending in `_s` (seconds) encode as nanosecond ints under the
    suffix-less name (`interval_s` -> `Interval`), matching Go
    `time.Duration` JSON encoding; decode also accepts Go duration strings.
  - Optional/None fields are omitted on encode.

Used by the jobspec JSON path, the HTTP API, and the api SDK.
"""

from __future__ import annotations

import dataclasses
import functools
import typing
from typing import Any, Dict, Optional, get_args, get_origin, get_type_hints

_ACRONYMS = {
    "id": "ID", "cpu": "CPU", "mb": "MB", "mhz": "MHz", "dc": "DC",
    "dcs": "DCs", "csi": "CSI", "acl": "ACL", "ip": "IP", "url": "URL",
    "ttl": "TTL", "tg": "TG", "gc": "GC", "http": "HTTP", "tls": "TLS",
    "ns": "NS", "rpc": "RPC", "os": "OS", "hcl": "HCL",
}

# Hand overrides where mechanical conversion diverges from the reference API.
_FIELD_OVERRIDES = {
    "memory_max_mb": "MemoryMaxMB",
    "mbits": "MBits",
    "port_label": "PortLabel",
    "ltarget": "LTarget",
    "rtarget": "RTarget",
    "node_class": "NodeClass",
}


@functools.lru_cache(maxsize=None)
def wire_name(py_name: str) -> str:
    if py_name in _FIELD_OVERRIDES:
        return _FIELD_OVERRIDES[py_name]
    dur = py_name.endswith("_s") and py_name not in ("status_s",)
    parts = py_name[:-2].split("_") if dur else py_name.split("_")
    return "".join(_ACRONYMS.get(p, p.capitalize()) for p in parts if p)


def _is_duration(py_name: str) -> bool:
    return py_name.endswith("_s")


# Values that encode as themselves; their exact types (no subclass), none
# of which is a dataclass
_PLAIN = frozenset((str, int, float, bool, type(None)))

# type -> its field plan, ((attribute, wire name, is duration), ...) in
# field order, for a dataclass type; None for any other type.  Worked out
# on a type's first encode: the event stream encodes every delivered
# event's payload, and the reflection (`dataclasses.fields`, a wire name
# split and joined) was paid per field of every one
_PLANS: Dict[type, Optional[tuple]] = {}


def _field_plan(tp: type) -> Optional[tuple]:
    plan = None
    if dataclasses.is_dataclass(tp):
        plan = tuple((f.name, wire_name(f.name), _is_duration(f.name))
                     for f in dataclasses.fields(tp))
    _PLANS[tp] = plan
    return plan


def encode(obj: Any) -> Any:
    """Dataclass/list/dict/scalar -> JSON-safe wire value."""
    tp = type(obj)
    if tp in _PLAIN:
        return obj
    try:
        plan = _PLANS[tp]
    except KeyError:
        plan = _field_plan(tp)
    if plan is not None:
        out: Dict[str, Any] = {}
        for attr, name, duration in plan:
            v = getattr(obj, attr)
            if v is None:
                continue
            if duration and isinstance(v, (int, float)):
                out[name] = int(v * 1e9)
            else:
                out[name] = v if type(v) in _PLAIN else encode(v)
        return out
    if isinstance(obj, dict):
        return {k: encode(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [encode(v) for v in obj]
    if isinstance(obj, bytes):
        import base64
        return base64.b64encode(obj).decode()
    return obj


def _strip_optional(tp):
    if get_origin(tp) is typing.Union:
        args = [a for a in get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


def decode(cls, data: Any):
    """Wire value -> instance of dataclass `cls` (recursive, tolerant of
    missing/extra fields)."""
    if data is None:
        return None
    tp = _strip_optional(cls)
    origin = get_origin(tp)
    if origin in (list, tuple):
        (item_tp,) = get_args(tp)[:1] or (Any,)
        seq = [decode(item_tp, v) for v in (data or [])]
        return tuple(seq) if origin is tuple else seq
    if origin is dict:
        args = get_args(tp)
        val_tp = args[1] if len(args) == 2 else Any
        return {k: decode(val_tp, v) for k, v in (data or {}).items()}
    if not (isinstance(tp, type) and dataclasses.is_dataclass(tp)):
        if tp is bytes and isinstance(data, str):
            import base64
            return base64.b64decode(data)
        return data
    hints = get_type_hints(tp)
    kwargs: Dict[str, Any] = {}
    by_wire = {wire_name(f.name): f for f in dataclasses.fields(tp)}
    lower = {k.lower(): k for k in (data or {})}
    for wname, f in by_wire.items():
        if wname in data:
            raw = data[wname]
        elif wname.lower() in lower:
            raw = data[lower[wname.lower()]]
        else:
            continue
        if _is_duration(f.name):
            kwargs[f.name] = _decode_duration(raw)
        else:
            kwargs[f.name] = decode(hints.get(f.name, Any), raw)
    return tp(**kwargs)


def _decode_duration(raw: Any) -> Optional[float]:
    if raw is None:
        return None
    if isinstance(raw, str):
        from nomad_tpu.jobspec.schema import parse_duration
        return parse_duration(raw)
    # Go time.Duration marshals to a nanosecond integer — always, even for
    # sub-millisecond values, so ints convert unconditionally (a 500_000
    # wire int is 0.5ms, not 500k seconds).  Floats only appear from our
    # own encoder, which writes seconds.
    if isinstance(raw, int):
        return raw / 1e9
    return float(raw)

"""The JSON wire codec's encode (structs/codec.py) against the reflective
encode it replaced, kept here as the reference: a field plan worked out
once a dataclass type must give the same dict, key order included, for
every dataclass in `nomad_tpu.structs`."""

import base64
import dataclasses
import importlib
import inspect
import json
import pkgutil
import typing

import pytest

import nomad_tpu.structs as structs_pkg
from nomad_tpu import mock
from nomad_tpu.structs import codec


def _reference_wire_name(py_name):
    if py_name in codec._FIELD_OVERRIDES:
        return codec._FIELD_OVERRIDES[py_name]
    dur = py_name.endswith("_s") and py_name not in ("status_s",)
    parts = py_name[:-2].split("_") if dur else py_name.split("_")
    return "".join(codec._ACRONYMS.get(p, p.capitalize())
                   for p in parts if p)


def reference_encode(obj):
    """The reflective encode: `dataclasses.fields` and a wire name
    worked out again for every field of every value."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out = {}
        for f in dataclasses.fields(obj):
            v = getattr(obj, f.name)
            if v is None:
                continue
            if f.name.endswith("_s") and isinstance(v, (int, float)):
                out[_reference_wire_name(f.name)] = int(v * 1e9)
            else:
                out[_reference_wire_name(f.name)] = reference_encode(v)
        return out
    if isinstance(obj, dict):
        return {k: reference_encode(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [reference_encode(v) for v in obj]
    if isinstance(obj, bytes):
        return base64.b64encode(obj).decode()
    return obj


def _dataclasses():
    mods = [structs_pkg] + [
        importlib.import_module(f"{structs_pkg.__name__}.{m.name}")
        for m in pkgutil.iter_modules(structs_pkg.__path__)]
    found = {}
    for mod in mods:
        for obj in vars(mod).values():
            if (inspect.isclass(obj) and dataclasses.is_dataclass(obj)
                    and obj.__module__.startswith(structs_pkg.__name__)):
                found[obj.__qualname__] = obj
    return found


_TYPES = _dataclasses()

# what an `Any` field holds here: nested containers, bytes, a tuple and a
# None inside a dict (kept: only a dataclass's None fields are omitted)
_ANY = {"b": b"\x00\xffraw", "t": (1, "a", 2.5), "n": None,
        "l": [1.5, {"x": b"z", "d": mock.eval()}]}


def _sample(tp, name, depth):
    """A value of type `tp` for field `name`: every container filled,
    nested dataclasses filled to a depth of two."""
    origin = typing.get_origin(tp)
    args = typing.get_args(tp)
    if origin is typing.Union:
        inner = [a for a in args if a is not type(None)]
        return _sample(inner[0], name, depth) if inner else None
    if origin in (list, typing.List):
        return [_sample(args[0] if args else typing.Any, name, depth)] * 2
    if origin is tuple:
        return tuple(_sample(a, name, depth) for a in args
                     if a is not Ellipsis) or ("t",)
    if origin in (dict, typing.Dict):
        val = args[1] if len(args) == 2 else typing.Any
        return {"k1": _sample(val, name, depth), "k2": None}
    if origin in (set, frozenset):
        return [_sample(args[0], name, depth)]
    if tp is typing.Any or tp is object:
        return _ANY
    if isinstance(tp, type) and dataclasses.is_dataclass(tp):
        return _filled(tp, depth + 1) if depth < 2 else tp()
    if tp is bool:
        return True
    if tp is int:
        return 7
    if tp is float:
        return 1.25
    if tp is str:
        return f"v-{name}"
    if tp is bytes:
        return b"\x01\x02"
    return None


def _filled(cls, depth=0):
    """An instance with every field set, every third one to None."""
    hints = typing.get_type_hints(cls)
    obj = cls()
    for i, f in enumerate(dataclasses.fields(cls)):
        value = (None if i % 3 == 2
                 else _sample(hints.get(f.name, typing.Any), f.name, depth))
        object.__setattr__(obj, f.name, value)
    return obj


def _same(got, want):
    # equal as values, and equal as text: json.dumps keeps key order (a
    # set, which neither encode converts, as a list in its own order)
    assert got == want
    assert json.dumps(got, default=list) == json.dumps(want, default=list)


@pytest.mark.parametrize("name", sorted(_TYPES))
def test_field_plan_encodes_as_the_reflective_encode(name):
    cls = _TYPES[name]
    for obj in (cls(), _filled(cls)):
        _same(codec.encode(obj), reference_encode(obj))
        # a second encode reads the plan it cached
        _same(codec.encode(obj), reference_encode(obj))


def _eval_with_failures():
    ev = mock.eval(status="blocked")
    ev.failed_tg_allocs = {"web": mock.alloc().metrics}
    ev.queued_allocations = {"web": 3}
    ev.related_evals = ["a", "b"]
    ev.wait_until = 12.5
    return ev


_MOCKS = {
    "job": mock.job,
    "batch_job": mock.batch_job,
    "system_job": mock.system_job,
    "node": mock.node,
    "alloc": mock.alloc,
    "eval": _eval_with_failures,
    "mixed_containers": lambda: [mock.eval(), (mock.node(), b"\x00"),
                                 {"j": mock.job(), "none": None}],
}


@pytest.mark.parametrize("name", sorted(_MOCKS))
def test_mock_objects_encode_as_the_reflective_encode(name):
    obj = _MOCKS[name]()
    _same(codec.encode(obj), reference_encode(obj))


def test_durations_and_classes():
    """`_s` fields to nanoseconds (an int one too), a dataclass CLASS
    passed through as itself, as the reflective encode does."""
    cls = next(c for c in _TYPES.values()
               if any(f.name.endswith("_s") for f in dataclasses.fields(c)))
    obj = cls()
    dur = next(f.name for f in dataclasses.fields(cls)
               if f.name.endswith("_s"))
    for value in (3, 0.5, 2.25):
        object.__setattr__(obj, dur, value)
        got = codec.encode(obj)
        assert got == reference_encode(obj)
        assert got[_reference_wire_name(dur)] == int(value * 1e9)
    assert codec.encode(cls) is cls is reference_encode(cls)

"""Minimal JSON<->dataclass mapping with unknown-key tolerance.

The reference stores pipeline state in ``ModelConfig.json`` / ``ColumnConfig.json``
(Jackson beans, reference ``container/obj/``).  We keep the exact camelCase key
contract so model sets written by the reference load here unchanged, and vice
versa.  Unknown keys are preserved round-trip in ``extra`` instead of erroring,
mirroring Jackson's permissive deserialization config.

``from_dict`` reads a class's annotations once: the first call for a class
builds its *plan* (field name -> converter), later calls look it up.  A
``ColumnConfig.json`` of 434 columns x 64 bins holds ~270k values:
annotations resolved per value would cost ten times the JSON parse.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import json
import typing
from typing import Any, Dict, Type, TypeVar, get_args, get_origin, get_type_hints

T = TypeVar("T")

_TRUE = ("true", "1", "yes")
_NUMBER = (int, float)
_UNKNOWN = object()             # a key that is not a field of the class

# class -> (field name -> converter, or None where the value is kept as
# is; whether the class has an ``extra`` field).  A race between threads
# builds a plan twice; both are the same.
_PLANS: Dict[type, tuple] = {}


def plans_built() -> int:
    """Conversion plans built in this process so far (one per class)."""
    return len(_PLANS)


def _unwrap_optional(tp):
    if get_origin(tp) is typing.Union:
        args = [a for a in get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


def _to_float(v):
    return float(v) if isinstance(v, _NUMBER) else v


def _to_int(v):
    return int(v) if isinstance(v, float) and v == int(v) else v


def _to_bool(v):
    return v.strip().lower() in _TRUE if isinstance(v, str) else v


def _converter(tp):
    """The function that coerces a JSON value into the annotated type
    ``tp``, or None where the value is kept as it is.  ``None`` passes
    through every converter."""
    tp = _unwrap_optional(tp)
    origin = get_origin(tp)
    if origin in (list, typing.List):
        (elem,) = get_args(tp) or (Any,)
        return _list_converter(_converter(elem))
    if origin in (dict, typing.Dict):
        args = get_args(tp)
        conv = _converter(args[1] if len(args) == 2 else Any)
        if conv is None:
            return lambda v: None if v is None else {k: x for k, x in v.items()}
        return lambda v: (None if v is None
                          else {k: conv(x) for k, x in v.items()})
    if isinstance(tp, type) and dataclasses.is_dataclass(tp):
        return functools.partial(from_dict, tp)
    if isinstance(tp, type) and issubclass(tp, enum.Enum):
        return lambda v: None if v is None else parse_enum(tp, v)
    if tp is float:
        return _to_float
    if tp is int:
        return _to_int
    if tp is bool:
        return _to_bool
    return None


def _list_converter(conv):
    # the element converters inlined: a bin list is one comprehension
    if conv is None:
        return lambda v: None if v is None else list(v)
    if conv is _to_float:
        return lambda v: None if v is None else [
            float(x) if isinstance(x, _NUMBER) else x for x in v]
    if conv is _to_int:
        return lambda v: None if v is None else [
            int(x) if isinstance(x, float) and x == int(x) else x for x in v]
    return lambda v: None if v is None else [conv(x) for x in v]


def _plan(cls) -> tuple:
    plan = _PLANS.get(cls)
    if plan is None:
        hints = get_type_hints(cls)
        names = [f.name for f in dataclasses.fields(cls)]
        plan = _PLANS[cls] = (
            {n: _converter(hints[n]) for n in names if n != "extra"},
            "extra" in names)
    return plan


def parse_enum(enum_cls, value):
    """Case-insensitive enum parse, accepting both names and values.

    Mirrors the reference's forgiving deserializers (e.g. ``NormTypeDeserializer``)
    which accept ``"zscale"``/``"ZSCALE"`` alike.
    """
    if isinstance(value, enum_cls):
        return value
    s = str(value).strip()
    for member in enum_cls:
        if member.name.lower() == s.lower() or str(member.value).lower() == s.lower():
            return member
    raise ValueError(f"{s!r} is not a valid {enum_cls.__name__} "
                     f"(choices: {[m.name for m in enum_cls]})")


def from_dict(cls: Type[T], data: Dict[str, Any]) -> T:
    """Build dataclass ``cls`` from a JSON dict; unknown keys land in ``extra``."""
    if data is None:
        return None
    convs, has_extra = _plan(cls)
    kwargs = {}
    extra = {}
    for key, value in data.items():
        conv = convs.get(key, _UNKNOWN)
        if conv is _UNKNOWN:
            extra[key] = value
        else:
            kwargs[key] = value if conv is None else conv(value)
    obj = cls(**kwargs)
    if extra and has_extra:
        obj.extra = extra
    return obj


def to_dict(obj) -> Any:
    """Dataclass -> JSON-ready dict (camelCase keys preserved, enums -> names)."""
    if obj is None:
        return None
    if dataclasses.is_dataclass(obj):
        out = {}
        for f in dataclasses.fields(obj):
            if f.name == "extra":
                continue
            out[f.name] = to_dict(getattr(obj, f.name))
        extra = getattr(obj, "extra", None)
        if extra:
            out.update(extra)
        return out
    if isinstance(obj, enum.Enum):
        return obj.name
    if isinstance(obj, list):
        return [to_dict(v) for v in obj]
    if isinstance(obj, dict):
        return {k: to_dict(v) for k, v in obj.items()}
    if isinstance(obj, float) and obj != obj:  # NaN is not valid JSON
        return None
    return obj


def dumps(obj, **kw) -> str:
    kw.setdefault("indent", 2)
    return json.dumps(to_dict(obj), **kw)


def loads(cls: Type[T], s: str) -> T:
    return from_dict(cls, json.loads(s))

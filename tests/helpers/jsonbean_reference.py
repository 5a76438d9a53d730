"""The per-value JSON -> dataclass mapping that ``config/jsonbean.py`` used
before it built one conversion plan per class, kept frozen: every annotation
is resolved again for every value.  ``tests/test_config.py`` holds the
planned ``from_dict`` to the objects this one builds."""

from __future__ import annotations

import dataclasses
import enum
import typing
from typing import Any, get_args, get_origin, get_type_hints


def _unwrap_optional(tp):
    if get_origin(tp) is typing.Union:
        args = [a for a in get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


def _coerce(tp, value):
    if value is None:
        return None
    tp = _unwrap_optional(tp)
    origin = get_origin(tp)
    if origin in (list, typing.List):
        (elem,) = get_args(tp) or (Any,)
        return [_coerce(elem, v) for v in value]
    if origin in (dict, typing.Dict):
        args = get_args(tp)
        vt = args[1] if len(args) == 2 else Any
        return {k: _coerce(vt, v) for k, v in value.items()}
    if isinstance(tp, type) and dataclasses.is_dataclass(tp):
        return from_dict(tp, value)
    if isinstance(tp, type) and issubclass(tp, enum.Enum):
        if isinstance(tp, type) and isinstance(value, tp):
            return value
        return parse_enum(tp, value)
    if tp is float and isinstance(value, (int, float)):
        return float(value)
    if tp is int and isinstance(value, float) and value == int(value):
        return int(value)
    if tp is bool and isinstance(value, str):
        return value.strip().lower() in ("true", "1", "yes")
    return value


def parse_enum(enum_cls, value):
    if isinstance(value, enum_cls):
        return value
    s = str(value).strip()
    for member in enum_cls:
        if member.name.lower() == s.lower() or str(member.value).lower() == s.lower():
            return member
    raise ValueError(f"{s!r} is not a valid {enum_cls.__name__} "
                     f"(choices: {[m.name for m in enum_cls]})")


def from_dict(cls, data):
    if data is None:
        return None
    hints = get_type_hints(cls)
    field_names = {f.name for f in dataclasses.fields(cls)}
    kwargs = {}
    extra = {}
    for key, value in data.items():
        if key in field_names and key != "extra":
            kwargs[key] = _coerce(hints[key], value)
        else:
            extra[key] = value
    obj = cls(**kwargs)
    if extra and "extra" in field_names:
        obj.extra = extra
    return obj

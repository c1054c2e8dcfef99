"""flags — gflags-style runtime configuration registry.

The reference configures everything through gflags with live reloading
(reloadable_flags.h:38-42) surfaced at /flags (builtin/flags_service) and
mirrored into bvars (bvar/gflag.h). This module is the same capability:
define typed flags, validate on set, edit live (the builtin console's /flags
endpoint writes through set_flag).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional


class Flag:
    __slots__ = ("name", "value", "default", "help", "type", "validator", "reloadable")

    def __init__(self, name, value, help_, type_, validator, reloadable):
        self.name = name
        self.value = value
        self.default = value
        self.help = help_
        self.type = type_
        self.validator = validator
        self.reloadable = reloadable


_registry: Dict[str, Flag] = {}
_lock = threading.Lock()
_on_set: Dict[str, List[Callable[[Any], None]]] = {}


def _define(name: str, default: Any, help_: str, type_: type,
            validator: Optional[Callable[[Any], bool]] = None,
            reloadable: bool = True) -> Flag:
    with _lock:
        if name in _registry:
            raise ValueError(f"flag {name!r} already defined")
        # Environment override: BRPC_TPU_<NAME>. Invalid or
        # validator-rejected values fall back to the default — an env var
        # must not be able to violate a flag's invariants.
        env = os.environ.get("BRPC_TPU_" + name.upper())
        value = default
        if env is not None:
            try:
                parsed = _parse(env, type_)
            except ValueError:
                import logging

                logging.getLogger(__name__).warning(
                    "ignoring unparsable env override for flag %s: %r", name, env
                )
            else:
                if validator is None or validator(parsed):
                    value = parsed
        f = Flag(name, value, help_, type_, validator, reloadable)
        _registry[name] = f
        return f


def _parse(text: str, type_: type) -> Any:
    if type_ is bool:
        return text.lower() in ("1", "true", "yes", "on")
    return type_(text)


def define_int(name: str, default: int, help_: str = "", **kw) -> Flag:
    return _define(name, int(default), help_, int, **kw)


def define_float(name: str, default: float, help_: str = "", **kw) -> Flag:
    return _define(name, float(default), help_, float, **kw)


def define_bool(name: str, default: bool, help_: str = "", **kw) -> Flag:
    return _define(name, bool(default), help_, bool, **kw)


def define_string(name: str, default: str, help_: str = "", **kw) -> Flag:
    return _define(name, str(default), help_, str, **kw)


def get_flag(name: str) -> Any:
    return _registry[name].value


def flag(name: str) -> Flag:
    return _registry[name]


def set_flag(name: str, value: Any) -> bool:
    """Live update (the /flags web editor path). Returns False if the flag is
    unknown, not reloadable, or fails validation."""
    with _lock:
        f = _registry.get(name)
        if f is None or not f.reloadable:
            return False
        if isinstance(value, str):
            try:
                value = _parse(value, f.type)
            except ValueError:
                return False
        if f.validator is not None and not f.validator(value):
            return False
        f.value = value
        hooks = list(_on_set.get(name, ()))
    for fn in hooks:
        fn(value)
    return True


def on_set(name: str, fn: Callable[[Any], None]):
    """Calls fn(new_value) after every successful set_flag(name, ...), for
    flags whose value must reach state outside this registry."""
    with _lock:
        _on_set.setdefault(name, []).append(fn)


def all_flags() -> Dict[str, Flag]:
    with _lock:
        return dict(_registry)

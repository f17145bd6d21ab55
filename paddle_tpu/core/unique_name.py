"""Unique name generator (reference: python/paddle/fluid/unique_name.py).

Keeps per-prefix counters inside a guard-able generator so cloned programs and
tests get reproducible names.
"""
from __future__ import annotations

import contextlib
from collections import defaultdict


class UniqueNameGenerator:
    def __init__(self, prefix: str = ""):
        self.prefix = prefix
        self.ids = defaultdict(int)

    def __call__(self, key: str) -> str:
        tmp = self.ids[key]
        self.ids[key] += 1
        return f"{self.prefix}{key}_{tmp}"


generator = UniqueNameGenerator()

# name_scope support (reference unique_name.py name_scope stack): a path of
# scope names prefixes every generated name WITHOUT resetting counters, and
# repeated sibling scopes dedup ("encoder", "encoder_1", ...)
_scope_stack: list = []
_scope_children: dict = defaultdict(lambda: defaultdict(int))


def generate(key: str) -> str:
    name = generator(key)
    if _scope_stack:
        return "/".join(_scope_stack) + "/" + name
    return name


def scope_path() -> str:
    """The `name_scope`s open now, outermost first, joined by `/`; empty outside any."""
    return "/".join(_scope_stack)


@contextlib.contextmanager
def name_scope_guard(prefix: str):
    parent = "/".join(_scope_stack)
    n = _scope_children[parent][prefix]
    _scope_children[parent][prefix] += 1
    unique = prefix if n == 0 else f"{prefix}_{n}"
    _scope_stack.append(unique)
    try:
        yield
    finally:
        _scope_stack.pop()


@contextlib.contextmanager
def guard(new_prefix: str = ""):
    """Swap in a fresh generator (used by Program.clone and tests)."""
    global generator
    old = generator
    generator = UniqueNameGenerator(new_prefix)
    try:
        yield
    finally:
        generator = old


def switch(new_generator=None):
    """reference unique_name.switch: swap the generator state, returning
    the old one (tests isolate name streams with it)."""
    global generator
    old = generator
    generator = new_generator if new_generator is not None else UniqueNameGenerator()
    return old

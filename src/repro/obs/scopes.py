"""Which named scope each instruction of a compiled program belongs to.

A device trace names each op by its HLO instruction name (``while.81``,
``fusion.227``, ``fused_sample.5``).  The compiled program's HLO text
pairs every instruction with the scope path JAX recorded for it::

    %while.81 = ... while(...), metadata={op_name="jit(fused_raw)/
        shmap_body/sample/level2/relabel/searchsorted/while" ...}

``op_scopes`` reads that pairing.  ``Recorded`` wraps a jitted function
so the program a driver ran can be compiled again from the abstract
arguments of its first call — a lookup in JAX's compilation caches —
and its map read after the fact; nothing else runs per call.

The instructions of the ``ENTRY`` computation (``top_level``) run one
after another; the ops of a ``while`` body run inside the ``while``'s
own interval, so summing the device time of the top-level ops under a
scope counts each instant once.
"""
from __future__ import annotations

import re

import jax

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\bmetadata=\{[^}]*?'
    r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def op_scopes(hlo_text: str, top_level: bool = False) -> dict[str, str]:
    """``{instruction name: op_name}`` for every instruction of
    ``hlo_text`` that carries an ``op_name`` in its metadata; with
    ``top_level``, those of the ``ENTRY`` computation only.

    >>> op_scopes('  %while.81 = s32[8]{0} while(%t), '
    ...           'metadata={op_name="jit(f)/sample/relabel/while"}')
    {'while.81': 'jit(f)/sample/relabel/while'}
    """
    out, entry = {}, False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            entry = True
        elif line.startswith("}"):
            entry = False
        elif not top_level or entry:
            m = _INSTRUCTION.match(line)
            if m:
                out[m.group(1)] = m.group(2)
    return out


def parts(op_name: str) -> set[str]:
    """The components of an op's scope path, each with a transform's
    wrapper taken off, so the backward pass of ``forward`` counts as
    ``forward``.

    >>> sorted(parts("jit(f)/transpose(jvp(forward))/layer0/dot_general"))
    ['dot_general', 'f', 'forward', 'layer0']
    """
    out = set()
    for part in op_name.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part:
            out.add(part)
    return out


def merge(maps) -> dict[str, str]:
    """One map over programs that run side by side.  Instruction names
    are unique only within a program, so a name two programs give
    different scopes is left out rather than guessed."""
    out: dict[str, str] = {}
    clash: set[str] = set()
    for m in maps:
        for name, scope in m.items():
            if out.get(name, scope) != scope:
                clash.add(name)
            out[name] = scope
    for name in clash:
        del out[name]
    return out


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.aval.weak_type,
            sharding=x.sharding if x.committed else None)
    return x


class Recorded:
    """A jitted function that keeps the abstract arguments (shapes,
    dtypes, shardings) of its first call, so ``op_scopes`` can read the
    program it compiled to.

    >>> f = Recorded(jax.jit(lambda x: x + 1))
    >>> _ = f(jax.numpy.ones(3))
    >>> any(s.endswith("/add") for s in f.op_scopes().values())
    True
    """

    __slots__ = ("fn", "args")

    def __init__(self, fn):
        self.fn = fn
        self.args = None

    def __call__(self, *args):
        if self.args is None:
            self.args = jax.tree.map(_abstract, args)
        return self.fn(*args)

    def op_scopes(self, top_level: bool = False) -> dict[str, str]:
        """The compiled program's ``op_scopes``; empty before the first
        call."""
        if self.args is None:
            return {}
        return op_scopes(self.fn.lower(*self.args).compile().as_text(),
                         top_level)

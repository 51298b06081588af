"""Named scopes of the LM step programs, and how to find them again.

Each scope is a `jax.named_scope`: metadata only, written into the
`op_name` of every instruction traced inside it (`metadata={op_name=...}`
in `Compiled.as_text()`), through `lax.scan` bodies, remat and the
backward pass.  A device trace names each op by its instruction, so the
compiled program's text maps the trace's ops to these scopes.

`SCOPES` are disjoint: an op carries at most one of them.  `KERNELS` sit
inside one of them and name the work that a Pallas kernel or its plain
jnp counterpart does, whichever implementation runs.  `PARTS` name a part
of one: `adapter`, a hybrid layer's low-rank adapter in the shared `mlp`;
`in_proj`, `mamba`'s input projection on a mesh that splits the SSM heads.
`hybrid` holds what a hybrid layer adds around its shared block: the
[x, x0] concat, its norm and the per-layer `linear`.
"""

from __future__ import annotations

import functools
import re

import jax

SCOPES = ("embed", "norm", "attn", "mlp", "moe", "mamba", "hybrid", "head",
          "optimizer")
KERNELS = ("sdpa", "ssd")
PARTS = ("adapter", "in_proj")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=(.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPCODE = re.compile(r" [a-z][\w\-]*\((\)?)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def scope(name: str):
    """The named scope `name`, one of SCOPES, KERNELS or PARTS."""
    if name not in SCOPES + KERNELS + PARTS:
        raise ValueError(f"unknown scope {name!r}")
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: the function's body runs inside scope `name`."""
    scope(name)

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def program_ops(hlo_text: str) -> dict:
    """From a compiled module's text (`Compiled.as_text()`): its name, which
    a device trace gives its runs, and each instruction's op_name.  An
    instruction that carries none, as the copies that the compiler adds to
    move a value between memory spaces, takes that of its first operand,
    whose value it moves; one with no operand either gets ""."""
    own, first = {}, {}
    for m in _INSTRUCTION.finditer(hlo_text):
        name, rest = m.groups()
        if op_name := _OP_NAME.search(rest):
            own[name] = op_name.group(1)
        elif (op := _OPCODE.search(rest)) and not op.group(1):
            operand = _OPERAND.search(rest, op.end())
            first[name] = operand.group(1) if operand else None
        else:
            first[name] = None
    ops = {}
    for name in list(own) + list(first):
        at, hops = name, 0
        while at in first and hops <= len(first):
            at, hops = first[at], hops + 1
        ops[name] = own.get(at, "")
    m = _MODULE.search(hlo_text)
    return {"module": m.group(1) if m else None, "ops": ops}


def segments(op_name: str) -> list:
    """The path segments of an op_name with transformation wrappers taken
    off: `transpose(jvp(attn))` -> `attn`.  Where XLA joined the names of
    merged instructions with `;`, the first stands for the op."""
    out = []
    for seg in op_name.split(";")[0].split("/"):
        while (m := _WRAPPED.match(seg)) and m.group(1):
            seg = m.group(1)
        out.append(seg)
    return out


def top_scope(op_name: str):
    """The one of SCOPES that the op_name lies in, or None."""
    return next((s for s in segments(op_name) if s in SCOPES), None)


def in_scope(op_name: str, name: str) -> bool:
    return name in segments(op_name)

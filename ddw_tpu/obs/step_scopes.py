"""Which layer each operation of a compiled train step belongs to.

A device profile names operations as the compiler did (``fusion.27``,
``while.3519``, ``flash_dkv.1``), and those names belong to no layer. The
program knows better: every instruction of the optimised HLO carries the name
stack it was traced under (``metadata={op_name="jit(_step)/shard_map/fwd_bwd/
transpose(jvp(TransformerLM))/.../checkpoint/rematted_computation/
backbone_block3/attn/attention/dot_general"}``), with the ``jax.named_scope``
names of the step and the models in it. :func:`step_table` reads that text
from the executable a trainer runs and gives one record a compiled step::

    {"module": "jit__step",
     "scopes": ["bwd/fwd_bwd/TransformerLM/backbone_block3/attn/attention",
                ...],
     "ops": {"fusion.27": 14, "while.3519": 3, "copy.8": -1, ...},
     "inside": {"while.3519": ["fusion.31", "while.3520", ...], ...},
     "unnamed_ops": 212,
     "seconds": {"compile": 21.3, "text": 0.4, "parse": 0.2}}

``ops`` holds every instruction the device can show as an operation of its
own — the entry computation's and, through them, the bodies of ``while``,
``conditional`` and ``call``, less parameters, constants, tuples and bitcasts
(not what lies inside a fusion: a fused operation is named by its own
``op_name``, its root's) — under the name the trace uses, with an index into
``scopes`` (-1: the compiler gave it no ``op_name``, or one that names no
place below the step; ``unnamed_ops`` counts those). ``inside`` lists, for
each instruction that calls computations, the instructions of those
computations, one level down, so that a reader can count a loop for its self
time and nothing twice. ``seconds`` (:func:`step_table` adds it) splits what
the table cost: the step's own compile or load, which the first dispatch is
then spared, and the text and the parse, which are what tracing adds.

A scope path is ``<pass>/<name stack>``: the ``op_name`` without ``jit(...)``,
with ``jvp(X)`` and ``transpose(X)`` read as ``X``, without the trailing
primitive, and first the pass it ran in — ``remat`` (``rematted_computation``
in the name: a checkpointed block's forward made again), else ``bwd``
(``transpose(``), else ``fwd``. Which component of a path is a layer is the
reader's to say (``benchmark/metrics/scope_time.py`` holds the benchmark's);
docs/observability.md lists the scopes the package opens.

The trainers' loop (``train/loop.py``) builds the table only under a tracer
and records it as the ``step_scopes`` span of ``tid="train"``, whose arguments
are the record. JAX leaves metadata out of the compile cache's key, so an
executable loaded from the cache carries the scopes of the tree that compiled
it: the table says what that executable holds, which is what runs.
"""

from __future__ import annotations

import re
import time

import jax

__all__ = ["step_table", "parse_hlo", "scope_path", "abstract"]

# parts of a name stack that name no place in the program
_NO_PLACE = frozenset(("checkpoint", "rematted_computation", "shard_map"))
_JIT = re.compile(r"jit\([^()]*\)")
_WRAPPER = re.compile(r"[\w.\-]+\(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the attributes through which a while, a conditional or a call names the
# computations it runs
_CALLED = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_CALLERS = frozenset(("while", "conditional", "call"))
# bookkeeping the device never shows as an operation: left out of ``ops``
_NO_TIME = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast"))


def scope_path(op_name: str) -> str:
    """``<pass>/<name stack>`` of one ``op_name`` (module docstring); empty
    where the stack names no place in the program (``jit(_step)/shard_map``
    and a primitive: what the compiler hung on the step itself)."""
    name = op_name.split(";", 1)[0]
    which = ("remat" if "rematted_computation" in name
             else "bwd" if "transpose(" in name else "fwd")
    while _JIT.search(name):
        name = _JIT.sub("", name)
    name = _WRAPPER.sub("", name).replace(")", "")
    parts = [p for p in name.split("/") if p and p not in _NO_PLACE][:-1]
    return "/".join([which] + parts) if parts else ""


def parse_hlo(text: str) -> dict:
    """The record of one optimised HLO module's text."""
    module = "?"
    computations: dict = {}     # name -> [(instruction, opcode, rest), ...]
    entry = current = None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
        elif line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                head = line.split("(", 1)[0].split()
                current = head[-1].lstrip("%")
                computations[current] = []
                if head[0] == "ENTRY":
                    entry = current
            else:
                current = None
        elif current is not None:
            m = _INSTRUCTION.match(line)
            if m:
                rest = m.group(2)
                op = _OPCODE.search(rest)
                computations[current].append(
                    (m.group(1), op.group(1) if op else "", rest))
    scopes: dict = {}
    ops: dict = {}
    inside: dict = {}
    todo, seen = [entry], {entry}
    while todo:
        for name, opcode, rest in computations.get(todo.pop(), ()):
            if opcode in _NO_TIME:
                continue
            m = _OP_NAME.search(rest)
            path = scope_path(m.group(1)) if m else ""
            ops[name] = scopes.setdefault(path, len(scopes)) if path else -1
            if opcode not in _CALLERS:
                continue
            called = _CALLED.findall(rest)
            for group in _BRANCHES.findall(rest):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            inside[name] = [n for c in called
                            for n, op, _ in computations.get(c, ())
                            if op not in _NO_TIME]
            for c in called:
                if c not in seen:
                    seen.add(c)
                    todo.append(c)
    return {"module": module, "scopes": list(scopes), "ops": ops,
            "inside": inside,
            "unnamed_ops": sum(1 for i in ops.values() if i < 0)}


def abstract(args):
    """``args`` as shapes: an array becomes its shape, dtype and weak type,
    with its sharding where it is committed to one — what ``jit`` keys an
    executable by, and nothing of the values."""
    def shape_of(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)
    return jax.tree.map(shape_of, args)


def step_table(step, args) -> dict | None:
    """The table of the executable ``step(*args)`` runs, for a jitted
    ``step``; None for a step that cannot be lowered (a test's double).

    Lowers and compiles ``step`` for ``args``' shapes, shardings and the
    step's own donation, reads the text and lets the executable go before it
    returns: a loaded executable keeps its scratch reserved, so the caller
    asks BEFORE the step's first dispatch and never holds two. The dispatch
    after it finds the executable this compile made in the process (``jit``
    and the ahead-of-time path share one in-memory cache of compilations,
    keyed by the lowered module) and neither compiles nor loads again; were
    that cache ever not shared, the dispatch would load the entry this
    compile wrote to the persistent cache, or compile a second time without
    one."""
    lower = getattr(step, "lower", None)
    if lower is None:
        return None
    t0 = time.monotonic()
    compiled = lower(*abstract(args)).compile()
    t1 = time.monotonic()
    text = compiled.as_text()
    del compiled
    t2 = time.monotonic()
    table = parse_hlo(text)
    # what the table cost: ``compile`` is the step's own compile or load,
    # which its first dispatch is then spared; ``text`` and ``parse`` are
    # what a traced fit pays on top of an untraced one
    table["seconds"] = {"compile": t1 - t0, "text": t2 - t1,
                        "parse": time.monotonic() - t2}
    return table

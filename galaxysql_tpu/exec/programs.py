"""The programs this process built, and the stage every instruction of one belongs to.

`exec/operators.py:_timed_first_call` records one `Program` a `global_jit`
program when its first call returns: its family, a digest of its key, the
ABSTRACT signature of that call (shapes, dtypes and shardings; no array is
kept), the first call's wall time and the span that launched it.  Nothing is
added to a dispatch: the timing wrapper is swapped out after the first call as
before.  The registry follows `_JIT_CACHE`: an entry goes when its program is
evicted.

`stages(program)` is what a reader asks after the fact (`SHOW PROGRAMS`, the
benchmark's `harness/stages.py` once the traced window has closed): it lowers
and compiles the program again from its signature (JAX's caches hold the
executable, so this costs milliseconds a program), reads `<instruction> = ...
op_name="..."` out of the compiled text and names each instruction after the
innermost `jax.named_scope` of `STAGES` its `op_name` holds, or `<family>/-`.
It never runs on a statement's path: a run nobody asks lowers nothing twice.

An executable that JAX's persistent cache handed back carries the metadata of
the process that compiled it: the cache's key leaves locations and names out
(`jax_compilation_cache_include_metadata_in_key` is off), so a scope added to
the source shows in a program's stages once that program is compiled anew."""

from __future__ import annotations

import dataclasses
import hashlib
import re
import threading
from typing import Dict, List, Optional, Tuple

import jax

from galaxysql_tpu.runtime import exec_platform

# Every `jax.named_scope` of the program's own (`tests/test_program_registry.py`
# holds this equal to the literals in the source).  A stage is metadata only:
# it changes no operation of the program it names.
STAGES = (
    "groupby/sort", "groupby/boundaries", "groupby/reduce", "groupby/matmul",
    "join_pairs/sort", "join_pairs/probe", "join_pairs/front",
    "join_pairs/expand", "join_pairs/verify",
    "sort/lexsort",
    "exchange/repartition", "exchange/compact", "exchange/broadcast",
    "join_block/gather", "join_block/matched",
)

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_ARRAY = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")


def instruction_key(line: str) -> Optional[str]:
    """How an instruction reads both in a compiled module's text and as the
    name of its event in a device profile (which is its whole HLO line):
    its name and the arrays of its result, layouts left out:
    `fusion.85 u32[6291456]`, `sort.23 u64[6356992],s32[6356992]`.  The result
    tells apart two programs of one family that differ by their shapes only;
    None for a line that defines no instruction."""
    m = _INSTRUCTION.match(line)
    if m is None:
        return None
    rest = m.group(2)
    depth = 0
    for i, ch in enumerate(rest):  # the result's type ends at the opcode
        depth += (ch == "(") - (ch == ")")
        if ch == " " and depth == 0:
            rest = rest[:i]
            break
    return f"{m.group(1)} {','.join(_ARRAY.findall(rest))}"


@dataclasses.dataclass
class Program:
    key: tuple
    family: str
    digest: str
    # the most slots (leading dimension) among the leaves of each positional
    # argument of the first call: `(1572864, 163840, 0)` is a join's build
    # batch, probe batch and literals
    slots: Tuple[int, ...]
    # `(treedef, specs)` of the first call, or None and `unsigned` says why
    signature: Optional[tuple]
    unsigned: str
    first_call_ms: float
    span: str           # the span that launched the first call, '' untraced
    trace_id: int
    # `instruction_key` -> stage for every instruction of the compiled module,
    # once a reader asked (`ProgramRegistry.stages`): what tells the module in
    # a device profile (the runtime's number after the module's name there is
    # no fingerprint the executable gives out) and what splits its seconds
    stages: Optional[Dict[str, str]] = None
    unstaged: str = ""  # why `stages` found nothing, once it was asked


def key_digest(key) -> str:
    """The key's scalar parts as they read (platform, capacities, kind; the
    family, its first element, is left out) and eight hex digits of the whole
    key: `tpu 1572864 #5f0c1a2b`.  As stable as `repr(key)` is, which is what
    the AOT cache names its files after."""
    parts = key[1:] if isinstance(key, tuple) else ()
    scalars = " ".join(str(k) for k in parts
                       if isinstance(k, (str, int, bool)))[:60]
    tail = hashlib.sha256(repr(key).encode()).hexdigest()[:8]
    return f"{scalars} #{tail}".lstrip()


def abstract_signature(args: tuple) -> Optional[tuple]:
    """`(treedef, specs)` of a call's positional arguments, such that lowering
    the program from it builds the module the call built (and JAX's caches
    answer): a `jax.ShapeDtypeStruct` for every array, Python scalars as they
    are; None where a leaf is neither.  A COMMITTED array's sharding is kept
    (a program whose arguments are sharded over a mesh has to be lowered for
    that sharding); an uncommitted one's is not, since the call itself
    lowered it unannotated: except under the TP path's CPU pin on an
    accelerator host, where the sharding is what says which platform the
    program ran on."""
    pinned = exec_platform() != jax.default_backend()
    leaves, treedef = jax.tree_util.tree_flatten(args)
    specs = []
    for leaf in leaves:
        if isinstance(leaf, (bool, int, float)):
            specs.append(leaf)
        elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            placed = pinned or getattr(leaf, "committed", False)
            specs.append(jax.ShapeDtypeStruct(
                tuple(int(d) for d in leaf.shape), leaf.dtype,
                sharding=getattr(leaf, "sharding", None) if placed else None,
                weak_type=getattr(leaf, "weak_type", False)))
        else:
            return None
    return treedef, tuple(specs)


def _slots(args: tuple) -> Tuple[int, ...]:
    return tuple(max((int(leaf.shape[0]) for leaf in jax.tree_util.tree_leaves(a)
                      if getattr(leaf, "shape", ())), default=0) for a in args)


def stage_of(op_name: str, family: str) -> str:
    """The innermost of `STAGES` in `op_name`; `<family>/-` under none."""
    at, stage, scopes = -1, f"{family}/-", op_name + "/"
    for s in STAGES:
        i = scopes.rfind(s + "/")
        if i > at:
            at, stage = i, s
    return stage


def parse_stages(text: str, family: str) -> Dict[str, str]:
    """`instruction_key` -> stage for every instruction of a compiled module's
    text; one that carries no `op_name` (a copy the compiler put in) is
    `<family>/-`."""
    out = {}
    for line in text.splitlines():
        key = instruction_key(line)
        if key is not None:
            m = _OP_NAME.search(line)
            out[key] = stage_of(m.group(1) if m else "", family)
    return out


class ProgramRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[tuple, Program] = {}   # in the order built

    def record(self, key: tuple, family: str, args: tuple, kwargs: dict,
               first_call_ms: float, unsigned: str = "", span: str = "",
               trace_id: int = 0) -> Program:
        signature = None
        if not unsigned and kwargs:
            unsigned = "called with keyword arguments"
        if not unsigned:
            try:
                signature = abstract_signature(args)
            except Exception as e:  # a first call must never fail for its record
                unsigned = f"signature failed: {type(e).__name__}"
            if signature is None and not unsigned:
                unsigned = "an argument is no array"
        p = Program(key, family, key_digest(key),
                    _slots(args) if signature is not None else (),
                    signature, unsigned, first_call_ms, span, trace_id)
        with self._lock:
            self._programs[key] = p
        return p

    def evict(self, key: tuple):
        with self._lock:
            self._programs.pop(key, None)

    def entries(self) -> List[Program]:
        with self._lock:
            return list(self._programs.values())

    def compile_ms_by_family(self) -> Dict[str, Tuple[int, float]]:
        """Family -> (programs, first-call milliseconds), the most first."""
        out: Dict[str, List[float]] = {}
        for p in self.entries():
            n_ms = out.setdefault(p.family, [0, 0.0])
            n_ms[0] += 1
            n_ms[1] += p.first_call_ms
        return {f: (int(n), ms) for f, (n, ms) in
                sorted(out.items(), key=lambda kv: -kv[1][1])}

    def stages(self, p: Program) -> Optional[Dict[str, str]]:
        """`p`'s instruction -> stage map, made once; None where it cannot be
        made, and `p.unstaged` then says why."""
        if p.stages is not None or p.unstaged:
            return p.stages
        from galaxysql_tpu.exec import operators as ops
        with ops._JIT_CACHE_LOCK:
            f = ops._JIT_CACHE.get(p.key)
        if p.signature is None:
            p.unstaged = "no signature: " + p.unsigned
        elif f is None or not hasattr(f, "lower"):
            p.unstaged = "evicted"
        else:
            treedef, specs = p.signature
            try:
                text = f.lower(*jax.tree_util.tree_unflatten(
                    treedef, specs)).compile().as_text()
            except Exception as e:  # a reader's question, never a failure
                p.unstaged = f"lowering failed: {type(e).__name__}: {e}"[:200]
            else:
                p.stages = parse_stages(text, p.family)
        return p.stages


PROGRAMS = ProgramRegistry()

"""The program registry (`exec/programs.py`): one entry a `global_jit` program,
written at its first call and nowhere else; `stages()` names every instruction
of a program's compiled module after the `jax.named_scope` that built it, only
when a reader asks; `SHOW PROGRAMS`, `compile:` spans and `chip_smoke.py` read
it."""

import ast
import collections
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.exec import programs as P
from galaxysql_tpu.exec.programs import PROGRAMS
from galaxysql_tpu.parallel import mpp
from galaxysql_tpu.parallel.mesh import make_mesh
from galaxysql_tpu.parallel.mpp import MppExecutor
from galaxysql_tpu.plan.physical import ExecContext
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.storage import tpch
from galaxysql_tpu.storage.tpch_queries import QUERIES

PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(ops.__file__)))
CHECKOUT = os.path.dirname(PACKAGE)
HINT = "/*+TDDL:FRAGMENT_CACHE(OFF)*/ "

LOWERINGS = [0]


def _count_lowerings(event, duration, **kw):
    """What `benchmarks/run.py:counters` reports as `jax_lowerings`."""
    if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        LOWERINGS[0] += 1


@pytest.fixture(scope="module")
def run(chip_formulation_module):
    """Tiny TPC-H Q3, Q5 and Q13 on the local engine under the TPU's
    formulations, traced, then Q3 on four virtual devices with every join down
    the shuffle exchange; the session, and what the registry and the caches
    held after each step."""
    jax.monitoring.register_event_duration_secs_listener(_count_lowerings)
    data = tpch.generate(0.01)
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    for t in tpch.TABLE_ORDER:
        s.execute(tpch.TPCH_DDL[t])
        inst.store("tpch", t).insert_arrays(data[t], inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE " + ", ".join(tpch.TABLE_ORDER))
    s.vars["ENABLE_QUERY_TRACING"] = 1
    spans = {}
    for q in (3, 5, 13):
        s.execute(HINT + QUERIES[q])
        spans[q] = list(s.last_spans)
    first = {"entries": len(PROGRAMS.entries()), "lowerings": LOWERINGS[0],
             "retraces": ops.COMPILE_STATS["retraces"]}
    for q in (3, 5, 13):
        s.execute(HINT + QUERIES[q])
    second = {"entries": len(PROGRAMS.entries()), "lowerings": LOWERINGS[0],
              "retraces": ops.COMPILE_STATS["retraces"]}
    plan = inst.planner.plan_select(QUERIES[3], "tpch")
    ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                      archive=inst.archive, archive_instance=inst)
    inst.frag_cache.clear()
    limit, mpp.BROADCAST_BUILD_LIMIT = mpp.BROADCAST_BUILD_LIMIT, 0
    try:
        MppExecutor(ctx, make_mesh(4)).execute(plan.rel)
    finally:
        mpp.BROADCAST_BUILD_LIMIT = limit
    yield {"session": s, "spans": spans, "first": first, "second": second}
    s.close()


def by_family(family):
    return [p for p in PROGRAMS.entries() if p.family == family]


def entry(key):
    return next((p for p in PROGRAMS.entries() if p.key == key), None)


def test_every_program_a_run_built_has_an_entry_with_its_family(run):
    with ops._JIT_CACHE_LOCK:
        cached = {k: f for k, f in ops._JIT_CACHE.items() if callable(f)}
    assert len(cached) > 15
    for key in cached:
        p = entry(key)
        assert p is not None, key[:2]
        assert p.family == ops.program_family(key)
        assert p.digest.endswith(P.key_digest(key)[-9:]) and "#" in p.digest
    assert {p.key for p in PROGRAMS.entries()} == set(cached)


def test_a_signature_holds_shapes_and_no_array(run):
    signed = [p for p in PROGRAMS.entries() if p.signature is not None]
    assert len(signed) > 15
    for p in signed:
        assert not p.unsigned
        for spec in p.signature[1]:
            assert isinstance(spec, (jax.ShapeDtypeStruct, bool, int, float)), \
                (p.family, type(spec))
        assert p.slots and all(isinstance(n, int) for n in p.slots)
        assert p.first_call_ms > 0


def test_a_host_closure_has_an_entry_without_a_signature_and_says_so(run):
    host = [p for p in PROGRAMS.entries() if p.family.endswith("_np")]
    assert host, sorted({p.family for p in PROGRAMS.entries()})
    for p in host:
        assert p.signature is None and p.unsigned == "a host closure"
        assert PROGRAMS.stages(p) is None and "no signature" in p.unstaged


def test_an_entry_names_the_span_and_the_statement_that_launched_it(run):
    pairs = by_family("join_pairs")
    assert pairs and all(p.span == "op:Join" for p in pairs)
    assert all(p.trace_id for p in pairs)
    assert {p.span for p in by_family("agg_partial")} == {"op:Aggregate"}
    assert {p.span for p in by_family("mpp_sjoin")} == {""}  # no statement


@pytest.mark.parametrize("family,stages", [
    ("join_pairs", {"join_pairs/sort", "join_pairs/probe", "join_pairs/expand",
                    "join_pairs/verify"}),
    ("agg_partial", {"groupby/sort", "groupby/boundaries", "groupby/reduce"}),
    ("mpp_sjoin", {"exchange/repartition", "join_block/gather",
                   "join_pairs/verify"}),
    ("mpp_agg", {"groupby/reduce"}),
])
def test_stages_names_the_scopes_that_built_a_program(run, family, stages):
    found = set()
    for p in by_family(family):
        got = PROGRAMS.stages(p)
        assert got is not None, (p.family, p.unstaged)
        assert set(got.values()) <= set(P.STAGES) | {family + "/-"}
        found |= set(got.values())
        assert PROGRAMS.stages(p) is got       # made once
    assert stages <= found, (family, found)


def test_the_stage_list_is_the_sources_named_scopes():
    literals = set()
    for base, _dirs, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(base, name)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "named_scope"):
                    assert len(node.args) == 1 and isinstance(
                        node.args[0], ast.Constant), (name, node.lineno)
                    literals.add(node.args[0].value)
    assert literals == set(P.STAGES)
    assert len(set(P.STAGES)) == len(P.STAGES)


@pytest.mark.parametrize("op_name,stage", [
    ("jit(join_pairs)/join_pairs/verify/gather", "join_pairs/verify"),
    ("jit(agg_partial)/groupby/reduce/jit(cumsum)/f", "groupby/reduce"),
    ("jit(join_pairs)/join_pairs/front/join_pairs/sort/sort", "join_pairs/sort"),
    ("jit(mpp_sjoin)/jit(shmap_body)/exchange/compact", "exchange/compact"),
    ("jit(join_pairs)/jit(argsort)/sort", "join_pairs/-"),
    ("", "join_pairs/-"),
])
def test_a_stage_is_the_innermost_scope_of_an_op_name(op_name, stage):
    assert P.stage_of(op_name, "join_pairs") == stage


@pytest.mark.parametrize("line,key", [
    ('  %fusion.85 = u32[6291456]{0:T(1024)S(1)} fusion(%x.1), kind=kLoop, '
     'metadata={op_name="jit(join_pairs)/join_pairs/probe/add"}',
     "fusion.85 u32[6291456]"),
    # a device profile names an event by the whole line, operand types too
    ("%fusion.85 = u32[6291456]{0:T(1024)} fusion(u32[6291456]{0} %x.1), "
     "kind=kLoop, calls=%fused_computation.7", "fusion.85 u32[6291456]"),
    ("  ROOT %sort.1 = (s32[1024]{0}, s64[1024]{0}) sort(%a, %b), "
     "dimensions={0}", "sort.1 s32[1024],s64[1024]"),
    ("%slice-start = ((s32[64]{0}), s32[16]{0}, s32[]{:S(2)}) async-start("
     "s32[64]{0} %x)", "slice-start s32[64],s32[16],s32[]"),
    ("HloModule jit_join_pairs, is_scheduled=true", None),
])
def test_an_instruction_reads_alike_in_the_text_and_in_a_profile(line, key):
    assert P.instruction_key(line) == key


def test_a_second_statement_adds_no_entry_and_lowers_nothing(run):
    assert run["first"]["entries"] > 15
    assert run["second"] == run["first"]


@pytest.mark.parametrize("q", [3, 5, 13])
def test_compile_spans_say_which_program_a_statement_compiled(run, q):
    compiles = [sp for sp in run["spans"][q] if sp.kind == "compile"]
    assert compiles
    digests = {p.digest for p in PROGRAMS.entries()}
    for sp in compiles:
        assert sp.attrs["program"] in digests, sp.attrs
        assert sp.attrs["wall_ms"] > 0


def test_show_programs_lists_the_registry_and_full_asks_for_stages(run):
    s = run["session"]
    rows = s.execute("SHOW PROGRAMS").rows
    assert len(rows) == len(PROGRAMS.entries())
    assert {r[0] for r in rows} >= {"join_pairs", "agg_partial", "segment",
                                    "mpp_sjoin"}
    before = LOWERINGS[0]
    rows = s.execute("SHOW FULL PROGRAMS LIKE 'sort'").rows
    assert rows and all(r[0] == "sort" for r in rows)
    family, digest, slots, first_ms, span, trace_id, stages = rows[0]
    assert "sort/lexsort:" in stages and span == "op:Sort" and first_ms > 0
    assert LOWERINGS[0] > before                # FULL lowered; plain did not
    assert all(r[6] for r in s.execute("SHOW PROGRAMS LIKE 'sort'").rows)


def test_eviction_takes_the_entry_with_the_program(monkeypatch):
    monkeypatch.setattr(ops, "_JIT_CACHE", collections.OrderedDict())
    monkeypatch.setattr(ops, "_JIT_CACHE_LIMIT", 2)
    keys = [("filter", "test_program_registry", i) for i in range(3)]
    try:
        for key in keys:
            ops.global_jit(key, lambda: ops.jit_program(lambda x: x + 1))(1)
        assert list(ops._JIT_CACHE) == keys[1:]
        assert entry(keys[0]) is None
        assert entry(keys[1]).family == "filter"
    finally:
        for key in keys:
            PROGRAMS.evict(key)


def test_lowering_from_the_signature_builds_the_module_the_call_built():
    """An uncommitted array (any result of an earlier program), a NumPy lane, a
    weakly typed scalar and a placed array: the text lowered from the
    signature is the call's own, so JAX's caches answer and the compiled
    module is the one a device profile shows."""
    key = ("filter", "test_program_registry", "signature")
    args = (jnp.arange(8), np.arange(8), jnp.asarray(2), 3,
            jax.device_put(jnp.arange(8), jax.devices()[1]))
    try:
        ops.global_jit(key, lambda: ops.jit_program(
            lambda a, b, c, d, e: a + b + c + d + e))(*args)
        treedef, specs = entry(key).signature
        assert [getattr(s, "sharding", None) is not None for s in specs] == \
            [False, False, False, False, True]
        assert specs[2].weak_type and not specs[0].weak_type
        program = ops._JIT_CACHE[key]
        assert program.lower(*jax.tree_util.tree_unflatten(
            treedef, specs)).as_text() == program.lower(*args).as_text()
    finally:
        with ops._JIT_CACHE_LOCK:
            ops._JIT_CACHE.pop(key, None)
        PROGRAMS.evict(key)


def test_a_call_with_keyword_arguments_is_recorded_without_a_signature():
    key = ("filter", "test_program_registry", "kw")
    try:
        ops.global_jit(key, lambda: ops.jit_program(lambda x: x + 1))(x=1)
        p = entry(key)
        assert p.signature is None
        assert p.unsigned == "called with keyword arguments"
    finally:
        with ops._JIT_CACHE_LOCK:
            ops._JIT_CACHE.pop(key, None)
        PROGRAMS.evict(key)


def test_chip_smokes_compile_table_reads_the_registry(run):
    table = PROGRAMS.compile_ms_by_family()
    assert table["join_pairs"][0] == len(by_family("join_pairs"))
    assert list(table.values()) == sorted(table.values(), key=lambda v: -v[1])
    assert sum(n for n, _ in table.values()) == len(PROGRAMS.entries())
    with open(os.path.join(CHECKOUT, "chip_smoke.py")) as f:
        source = f.read()
    assert "PROGRAMS.compile_ms_by_family()" in source
    assert not hasattr(ops, "COMPILE_MS_BY_PROGRAM")

"""The program's spans on the profiler's clock: while a `jax.profiler` session
records, a statement's phase ramp, operator pulls, segment dispatches and MPP
stages are entered as `TraceAnnotation`s carrying its `trace_id`; while none
does, a statement pays one check and allocates nothing."""

import glob
import os

import jax
import pytest

from benchmarks.harness import spans as S
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.utils import errors, tracing

AGG = "select g, sum(v) from t where v > 10 group by g order by g"
POINT = "select v from t where id = {}"


@pytest.fixture(scope="module")
def served():
    inst = Instance()
    s = Session(inst)
    s.execute("create database d")
    s.execute("use d")
    s.execute("create table t (id int primary key, v int, g int)")
    s.execute("insert into t values " +
              ",".join(f"({i},{i * 2},{i % 5})" for i in range(500)))
    s.execute(AGG)
    for i in (1, 2):
        s.execute(POINT.format(i))  # registers, then serves, the point plan
    yield inst, s
    s.close()


@pytest.fixture
def profiled(tmp_path):
    """Run `body()` under a profiler session; the recorded trace as
    `benchmarks/harness/spans.py` reads it."""
    def run(body):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        return S.load(path)
    return run


def test_device_trace_active_follows_the_profiler_session(tmp_path):
    """The one reading of JAX's state: if JAX moves the switch, this fails."""
    assert tracing.device_trace_active() is False
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.device_trace_active() is True
    finally:
        jax.profiler.stop_trace()
    assert tracing.device_trace_active() is False


def test_a_statements_spans_reach_the_profilers_trace(served, profiled):
    inst, s = served
    inst.trace_store.configure(rate=0.0)  # the head sampler says no
    ids = []

    def body():
        with jax.profiler.TraceAnnotation("bench_stmt:agg"):
            rows = s.execute(AGG).rows
        ids.append(int(s.last_trace[0].split()[-1]))
        assert rows[0] == (0, 49490)

    trace = profiled(body)
    thread, = [th for th in trace["threads"]
               if any(sp[0] == "phase:plan" for sp in th)]
    mine = [sp for sp in thread if sp[3] == ids[0]]
    names = [sp[0] for sp in mine]
    for phase in ("phase:fence_wait", "phase:admission", "phase:queue",
                  "phase:plan", "phase:execute", "phase:serialize", "query"):
        assert names.count(phase) == 1, (phase, names)
    assert any(n.startswith("op:") for n in names), names
    by_name = {sp[0]: sp for sp in mine}
    stmt, = trace["statements"]
    for name in ("phase:plan", "phase:execute", "phase:serialize"):
        assert stmt[1] <= by_name[name][1] and by_name[name][2] <= stmt[2]
        assert by_name["query"][1] <= by_name[name][1] \
            and by_name[name][2] <= by_name["query"][2]
    # every operator pull sits inside the execute phase, on the same clock
    x = by_name["phase:execute"]
    assert all(x[1] <= sp[1] and sp[2] <= x[2]
               for sp in mine if sp[0].startswith("op:"))
    assert by_name["phase:plan"][2] <= x[1] <= x[2] \
        <= by_name["phase:serialize"][1]
    # the same statement has the same tree in the program's own ring,
    # whatever the sampler said: operators under the execute phase
    tree = {sp.span_id: sp for sp in s.last_spans}
    phases = {sp.name: sp for sp in s.last_spans if sp.kind == "phase"}
    assert set(phases) == {"plan", "execute", "serialize"}
    ops_ = [sp for sp in s.last_spans if sp.kind == "operator"]
    assert ops_ and all(
        tree[sp.parent_id].kind in ("phase", "operator") for sp in ops_)
    assert S.self_seconds(trace["threads"], "phase:plan") > 0


def test_the_point_path_is_annotated_too(served, profiled):
    inst, s = served
    inst.trace_store.configure(rate=0.0)
    trace = profiled(lambda: s.execute(POINT.format(9)))
    tid = int(s.last_trace[0].split()[-1])
    names = [sp[0] for th in trace["threads"] for sp in th if sp[3] == tid]
    assert "phase:execute" in names and "query" in names
    assert "phase:plan" not in names  # a point plan skips the planner


def test_an_error_closes_the_annotations_it_skipped(served, profiled):
    inst, s = served

    def body():
        with pytest.raises(errors.TddlError):
            s.execute("select nope from t group by g")
        assert s._ann is None
        s.execute(AGG)

    trace = profiled(body)
    thread, = [th for th in trace["threads"]
               if any(sp[0] == "phase:plan" for sp in th)]
    queries = [sp for sp in thread if sp[0] == "query"]
    plans = [sp for sp in thread if sp[0] == "phase:plan"]
    assert len(queries) == 2 and len(plans) == 2
    # the failed statement's plan phase ended with its root, not at exit
    assert plans[0][2] <= queries[0][2] <= queries[1][1]


def test_no_session_no_sample_nothing_allocated(served, monkeypatch):
    """Profiler off and the digest unsampled: no TraceContext, no annotation,
    on the planned path and on the point path."""
    inst, s = served
    inst.trace_store.configure(rate=0.0)
    made = []

    class Counted(tracing.TraceContext):
        def __init__(self, *a, **k):
            made.append("context")
            super().__init__(*a, **k)

    class CountedAnnotation(tracing._TraceAnnotation):
        def __init__(self, *a, **k):
            made.append("annotation")
            super().__init__(*a, **k)

    monkeypatch.setattr(tracing, "TraceContext", Counted)
    monkeypatch.setattr(tracing, "_TraceAnnotation", CountedAnnotation)
    assert not tracing.device_trace_active()
    assert s.execute(AGG).rows[0] == (0, 49490)
    assert s.execute(POINT.format(11)).rows == [(22,)]
    assert made == [] and s._ann is None and s.last_spans == []
    # the counters do count: an explicit opt-in builds a context (and, with
    # no session recording, still no annotation)
    s.execute("set ENABLE_QUERY_TRACING = 1")
    try:
        s.execute(AGG)
    finally:
        s.execute("set ENABLE_QUERY_TRACING = 0")
        s.vars.pop("ENABLE_QUERY_TRACING", None)
    assert made == ["context"]


def test_mesh_stages_and_shards_are_annotated(profiled):
    from galaxysql_tpu.parallel.mesh import make_mesh
    from galaxysql_tpu.parallel.mpp import MppExecutor
    from galaxysql_tpu.plan.physical import ExecContext
    inst = Instance()
    s = Session(inst)
    s.execute("create database m")
    s.execute("use m")
    s.execute("create table f (k bigint, v bigint) partition by hash(k) "
              "partitions 4")
    inst.store("m", "f").insert_pylists(
        {"k": list(range(4000)), "v": [i % 7 for i in range(4000)]},
        inst.tso.next_timestamp())
    plan = inst.planner.plan_select(
        "select v, count(*) from f group by v", "m")
    out = []

    def body():
        tc = tracing.TraceContext(77, annotate=tracing.device_trace_active())
        ctx = ExecContext(inst.stores, inst.tso.next_timestamp(), [],
                          archive=inst.archive, archive_instance=inst)
        with tracing.activate(tc):
            out.append(MppExecutor(ctx, make_mesh(4)).execute(plan.rel))

    trace = profiled(body)
    s.close()
    assert len(out[0].to_pylist()) == 7
    names = [sp[0] for th in trace["threads"] for sp in th if sp[3] == 77]
    assert any(n.startswith("stage:") for n in names), names
    assert not any(n.startswith("stage:mpp:") for n in names)
    assert {"shard:0", "shard:1", "shard:2", "shard:3"} <= set(names)
    assert any(n == "transfer:f" for n in names), names

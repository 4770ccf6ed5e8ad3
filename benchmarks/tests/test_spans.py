"""The arithmetic of `harness/spans.py` on synthetic lists: self time of a phase
less its children, idle time inside `phase:execute`, families and groups, the
unnamed share and the sum check; the eight readers on a run object built by
hand, with and without the program's spans; and the loader on a trace recorded
here (the CPU backend has no device plane: the host spans are what it shows)."""

import os
import types

import pytest

from benchmarks.harness import spans as S
from benchmarks.harness import trace as T
from benchmarks.harness.byname import load_module

METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "metrics")


def thread():
    # one statement (trace id 7) on one server thread, window 0-10
    return [("phase:admission", 0.10, 0.11, 7),
            ("query", 0.2, 9.0, 7),
            ("phase:plan", 0.2, 1.2, 7),
            ("op:Scan", 0.4, 0.7, 7),            # a subquery run while planning
            ("segment:filter", 0.5, 0.6, 7),     # nested in the op: counted once
            ("phase:execute", 1.2, 8.0, 7),
            ("op:Aggregate", 1.3, 7.9, 7),
            ("op:Join", 1.4, 6.0, 7),
            ("phase:serialize", 8.0, 8.5, 7)]


def ops_and_modules():
    ops = [("%fusion.1 = fusion()", 2.0, 3.0),
           ("%while.2 = while()", 3.0, 5.0),
           ("%fusion.3 = fusion()", 3.5, 4.5),     # the loop's body
           ("%sort.4 = sort()", 6.5, 7.0),
           ("%fusion.5 = fusion()", 7.0, 7.25),
           ("%convert.6 = convert()", 8.1, 8.2),
           ("%copy.7 = copy()", 9.5, 9.75)]
    modules = [("jit_join_pairs(8114710924276274526)", 1.9, 5.1),
               ("jit_agg_partial(77)", 6.4, 7.1),
               ("jit_segment(5)", 6.95, 7.3),
               ("jit_convert_element_type(9)", 8.05, 8.25)]
    return ops, modules


def raw():
    ops, modules = ops_and_modules()
    return {"threads": [thread()], "statements": [(T.STATEMENT + "q3", 0.0, 9.2)],
            "host": [(T.WINDOW, 0.0, 10.0)], "ops": ops, "modules": modules}


def test_self_time_of_a_phase_is_less_its_children():
    assert S.self_seconds([thread()], "phase:plan") == pytest.approx(1.0 - 0.3)
    assert S.self_seconds([thread()], "phase:serialize") == pytest.approx(0.5)
    # nested spans count once; spans of two threads add up
    assert S.self_seconds([thread(), thread()], "phase:execute") == \
        pytest.approx(2 * (6.8 - 6.6))
    assert S.self_seconds([thread()], "phase:compile") is None
    assert S.self_seconds([], "phase:plan") is None


def test_idle_inside_execute():
    busy = [[2.0, 5.0], [6.5, 7.25], [8.1, 8.2]]
    execute = [sp for sp in thread() if sp[0] == "phase:execute"]
    assert S.idle_inside(busy, execute) == pytest.approx(6.8 - 3.0 - 0.75)
    assert S.idle_inside([], execute) == pytest.approx(6.8)
    assert S.idle_inside([[0.0, 10.0]], execute) == pytest.approx(0.0)
    assert S.idle_inside(busy, []) is None
    # an interval that starts before the span and ends inside it
    assert S.idle_inside([[1.0, 2.0]], [("phase:execute", 1.5, 3.0, 1)]) == \
        pytest.approx(1.0)


@pytest.mark.parametrize("module,family", [
    ("jit_join_pairs(8114710924276274526)", "join_pairs"),
    ("jit_segment(5)", "segment"),
    ("jit_mpp_agg_salt", "mpp_agg_salt"),
    ("jit_run(123)", "run"),
    ("pjit_something", ""),
    ("", ""),
])
def test_family_of_a_module(module, family):
    assert S.family_of(module) == family


def test_every_family_is_in_one_group():
    assert set(S.FAMILY_GROUP.values()) == set(S.GROUPS)
    assert "run" not in S.FAMILY_GROUP and "spmd" not in S.FAMILY_GROUP


def test_family_seconds_group_and_add_up_to_the_busy_time():
    ops, modules = ops_and_modules()
    fam = S.family_seconds(ops, modules)
    assert fam["join"] == pytest.approx(3.0)       # fusion.1 + while.2 (self 1.0 + body 1.0)
    assert fam["agg"] == pytest.approx(0.5)
    assert fam["scan"] == pytest.approx(0.25)      # the op starts in jit_segment
    assert fam["sort"] == 0.0
    assert fam["unnamed"] == pytest.approx(0.1 + 0.25)  # an eager op, and no module
    assert fam["by_family"]["jit_convert_element_type(9)"] == pytest.approx(0.1)
    assert fam["by_family"]["no_module"] == pytest.approx(0.25)
    busy = sum(e - s for s, e in T.merge_intervals((s, e) for _, s, e in ops))
    assert sum(fam[g] for g in S.GROUPS) + fam["unnamed"] == pytest.approx(busy)


def test_a_program_that_names_nothing_is_all_unnamed():
    ops, modules = ops_and_modules()
    old = [("jit_run(%d)" % i, s, e) for i, (_, s, e) in enumerate(modules)]
    fam = S.family_seconds(ops, old)
    assert all(fam[g] == 0.0 for g in S.GROUPS) and fam["unnamed"] > 0


def run_of(raw_trace, statements=1):
    return types.SimpleNamespace(
        trace={"busy_s_chip0": 4.1}, state={"spans": S.reduce_spans(raw_trace)},
        window={"traced": {"statements": statements, "client_s": 9.2}})


def read(name, run):
    return load_module(os.path.join(METRICS, name + ".py")).read(run)


def test_the_readers_on_a_run_with_spans():
    run = run_of(raw())
    assert read("ap_plan_ms_per_stmt", run) == pytest.approx(700.0)
    assert read("ap_serialize_ms_per_stmt", run) == pytest.approx(500.0)
    assert read("ap_execute_idle_s_per_stmt", run) == pytest.approx(3.05)
    four = [read(f"ap_{g}_device_s_per_stmt", run) for g in S.GROUPS]
    assert four == pytest.approx([3.0, 0.5, 0.0, 0.25])
    share = read("ap_unnamed_device_share", run)
    assert share == pytest.approx(100 * 0.35 / 4.1)
    # the sum check: four groups and the unnamed time are the busy time
    assert sum(four) + share / 100 * 4.1 == pytest.approx(
        run.state["spans"]["busy_s"])


def test_the_readers_on_a_program_that_enters_no_span():
    """An older commit: modules called jit_run, no annotation.  Nothing raises;
    the span metrics are left out and the device time is all unnamed."""
    r = raw()
    r["threads"] = []
    r["modules"] = [("jit_run(1)", 1.9, 9.9)]
    run = run_of(r)
    for name in ("ap_plan_ms_per_stmt", "ap_serialize_ms_per_stmt",
                 "ap_execute_idle_s_per_stmt"):
        assert read(name, run) is None
    assert read("ap_join_device_s_per_stmt", run) == 0.0
    assert read("ap_unnamed_device_share", run) == pytest.approx(100.0)
    untraced = types.SimpleNamespace(trace=None, state={}, window={})
    assert read("ap_unnamed_device_share", untraced) is None
    assert read("ap_plan_ms_per_stmt", untraced) is None


def test_window_clips_spans_and_operations():
    r = raw()
    r["host"] = [(T.WINDOW, 0.0, 7.0)]
    red = S.reduce_spans(r)
    assert red["busy_s"] == pytest.approx(3.0 + 0.5)
    assert [sp[0] for sp in red["threads"][0]].count("phase:serialize") == 0


def test_loader_reads_spans_and_trace_ids_from_a_recorded_trace(tmp_path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(T.WINDOW):
        with jax.profiler.TraceAnnotation(T.STATEMENT + "q1"):
            with jax.profiler.TraceAnnotation("phase:plan", trace_id=1 << 61):
                with jax.profiler.TraceAnnotation("op:Scan", trace_id=1 << 61):
                    jax.numpy.arange(8).block_until_ready()
    jax.profiler.stop_trace()
    got = S.load(T.newest_xplane(str(tmp_path)))
    spans = [sp for th in got["threads"] for sp in th]
    assert [sp[0] for sp in spans] == ["phase:plan", "op:Scan"]
    assert {sp[3] for sp in spans} == {1 << 61}
    assert [e[0] for e in got["statements"]] == [T.STATEMENT + "q1"]
    assert got["host"][0][0] == T.WINDOW and got["ops"] == []
    assert S.self_seconds(got["threads"], "phase:plan") < \
        spans[0][2] - spans[0][1]

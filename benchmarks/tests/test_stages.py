"""The arithmetic of `harness/stages.py` on synthetic lists: a module matched to
the one program that knows its instructions, seconds by stage that add up to
the busy time, a module two programs split differently counted whole as
unmatched, the match by name alone where a result's arrays find nothing; the
ten readers on a run object built by hand, and on a program without a
registry."""

import os
import types

import pytest

from benchmarks.harness import stages as G
from benchmarks.harness.byname import load_module

METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "metrics")
READERS = ("ap_join_lookup_device_s_per_stmt", "ap_join_expand_device_s_per_stmt",
           "ap_join_verify_device_s_per_stmt", "ap_groupby_sort_device_s_per_stmt",
           "ap_groupby_boundaries_device_s_per_stmt",
           "ap_groupby_reduce_device_s_per_stmt",
           "mpp_repartition_device_s_per_stmt", "mpp_compact_device_s_per_stmt",
           "ap_unstaged_device_share", "ap_stage_unmatched_device_share")


def key_of(name):
    """`%fusion.1 = u32[8] fusion()` -> `fusion.1 u32[8]`, as the program's
    `instruction_key` reads a trace's event."""
    head, rest = name.split(" = ", 1)
    return f"{head.lstrip('%')} {rest.split(' ')[0]}"


def ops_and_modules():
    ops = [("%sort.1 = u64[8] sort()", 2.0, 3.0),           # pairs, 3.0 busy
           ("%while.2 = s32[8] while()", 3.0, 5.0),
           ("%fusion.3 = s32[8] fusion()", 3.5, 4.5),        # the loop's body
           ("%sort.1 = u64[4] sort()", 5.2, 5.7),            # the small pairs
           ("%fusion.4 = s64[8] fusion()", 6.5, 7.0),        # agg, 0.55 busy
           ("%copy.5 = s64[8] copy()", 7.0, 7.05),
           ("%all-to-all.6 = u32[8] all-to-all()", 7.5, 8.0),  # sjoin, 1.0
           ("%fusion.7 = u32[8] fusion()", 8.0, 8.5),
           ("%convert.8 = f32[8] convert()", 9.0, 9.25)]     # eager: 0.25
    modules = [("jit_join_pairs(8114710924276274526)", 1.9, 5.1),
               ("jit_join_pairs(42)", 5.15, 5.8),
               ("jit_agg_partial(77)", 6.4, 7.3),
               ("jit_mpp_sjoin(5)", 7.4, 8.6),
               ("jit_convert_element_type(9)", 8.9, 9.3)]
    return ops, modules


def program(family, digest, instructions, **kw):
    return dict({"family": family, "program": digest, "slots": [8, 8, 0],
                 "span": "op:Join", "trace_id": 7,
                 "instructions": instructions}, **kw)


def programs():
    return [
        program("join_pairs", "tpu 8 #aa", {
            "sort.1 u64[8]": "join_pairs/sort", "while.2 s32[8]": "join_pairs/expand",
            "fusion.3 s32[8]": "join_pairs/verify"}),
        program("join_pairs", "tpu 4 #bb", {
            "sort.1 u64[4]": "join_pairs/sort", "fusion.3 s32[4]": "join_pairs/verify"}),
        program("agg_partial", "tpu 8 #cc", {
            "fusion.4 s64[8]": "groupby/reduce"}, span="op:Aggregate"),
        program("mpp_sjoin", "inner 4 #dd", {
            "all-to-all.6 u32[8]": "exchange/repartition",
            "fusion.7 u32[8]": "exchange/compact"}, span="stage:Join"),
        program("mpp_sjoin", "never lowered #ee", None),
    ]


def reduced():
    return G.reduce_stages(*ops_and_modules(), programs(), key_of)


def test_stages_and_unmatched_seconds_add_up_to_the_busy_time():
    got = reduced()
    assert got["stages"] == pytest.approx({
        "join_pairs/sort": 1.0 + 0.5, "join_pairs/expand": 1.0,
        "join_pairs/verify": 1.0, "groupby/reduce": 0.5,
        "agg_partial/-": 0.05,            # a copy the text does not know
        "exchange/repartition": 0.5, "exchange/compact": 0.5})
    assert got["unmatched_s"] == pytest.approx(0.25)   # the eager convert
    assert got["busy_s"] == pytest.approx(5.3)
    assert sum(got["stages"].values()) + got["unmatched_s"] == \
        pytest.approx(got["busy_s"])


def test_a_module_reads_as_its_program_its_slots_and_its_launching_span():
    rows = {r["module"]: r for r in reduced()["modules"]}
    big = rows["jit_join_pairs.274526"]
    assert (big["program"], big["slots"], big["span"], big["trace_id"]) == \
        ("tpu 8 #aa", [8, 8, 0], "op:Join", 7)
    assert big["candidates"] == 1 and big["coverage"] == pytest.approx(1.0)
    assert big["by"] == "name and result"
    # the result's arrays tell two programs of one family apart
    assert rows["jit_join_pairs.42"]["program"] == "tpu 4 #bb"
    assert rows["jit_agg_partial.77"]["coverage"] == pytest.approx(0.5 / 0.55)
    assert "program" not in rows["jit_convert_element_type.9"]
    assert [r["seconds"] for r in reduced()["modules"]] == \
        sorted((r["seconds"] for r in rows.values()), reverse=True)


def test_under_the_coverage_a_module_is_unmatched_not_guessed():
    ops, modules = ops_and_modules()
    ops[5] = ("%copy.5 = s64[8] copy()", 7.0, 7.29)   # the copy: over a tenth
    known = programs()
    known[2]["instructions"] = {"copy.5 s64[8]": "groupby/sort"}
    got = G.reduce_stages(ops, modules, known, key_of)
    assert "groupby/sort" not in got["stages"]
    assert got["unmatched_s"] == pytest.approx(0.25 + 0.79)


def test_two_programs_that_split_a_module_differently_leave_it_unmatched():
    known = programs()
    twin = program("join_pairs", "tpu 8 #ff", dict(known[0]["instructions"]))
    got = G.reduce_stages(*ops_and_modules(), known + [twin], key_of)
    rows = {r["module"]: r for r in got["modules"]}
    # maps that agree: a tie is no doubt
    assert rows["jit_join_pairs.274526"]["candidates"] == 2
    assert got["stages"]["join_pairs/verify"] == pytest.approx(1.0)
    twin["instructions"]["fusion.3 s32[8]"] = "join_pairs/probe"
    got = G.reduce_stages(*ops_and_modules(), known + [twin], key_of)
    rows = {r["module"]: r for r in got["modules"]}
    assert "program" not in rows["jit_join_pairs.274526"]
    assert got["unmatched_s"] == pytest.approx(0.25 + 3.0)
    assert "join_pairs/expand" not in got["stages"]
    assert got["stages"]["join_pairs/sort"] == pytest.approx(0.5)


def test_a_name_alone_matches_where_the_results_arrays_find_nothing():
    known = programs()
    known[3]["instructions"] = {"all-to-all.6 u32[4,2]": "exchange/repartition",
                                "fusion.7 u32[4,2]": "exchange/compact"}
    got = G.reduce_stages(*ops_and_modules(), known, key_of)
    row = next(r for r in got["modules"] if r["family"] == "mpp_sjoin")
    assert row["by"] == "name" and row["program"] == "inner 4 #dd"
    assert got["stages"]["exchange/compact"] == pytest.approx(0.5)


def run_of(got, statements=2):
    got = dict(got, statements=statements)
    return types.SimpleNamespace(trace={"busy_s_chip0": got["busy_s"]},
                                 state={"stages": got}, out_dir="",
                                 window={"traced": {"statements": statements}})


def read(name, run):
    return load_module(os.path.join(METRICS, name + ".py")).read(run)


def test_the_ten_readers_add_up_to_the_busy_seconds_a_statement():
    run = run_of(reduced())
    got = {name: read(name, run) for name in READERS}
    assert got["ap_join_lookup_device_s_per_stmt"] == pytest.approx(0.75)
    assert got["ap_join_expand_device_s_per_stmt"] == pytest.approx(0.5)
    assert got["ap_join_verify_device_s_per_stmt"] == pytest.approx(0.5)
    assert got["ap_groupby_sort_device_s_per_stmt"] == 0.0
    assert got["ap_groupby_reduce_device_s_per_stmt"] == pytest.approx(0.25)
    assert got["mpp_repartition_device_s_per_stmt"] == pytest.approx(0.25)
    assert got["mpp_compact_device_s_per_stmt"] == pytest.approx(0.25)
    assert got["ap_unstaged_device_share"] == pytest.approx(100 * 0.05 / 5.3)
    assert got["ap_stage_unmatched_device_share"] == \
        pytest.approx(100 * 0.25 / 5.3)
    seconds = sum(v for k, v in got.items() if k.endswith("_s_per_stmt"))
    shares = (got["ap_unstaged_device_share"]
              + got["ap_stage_unmatched_device_share"]) / 100
    assert seconds + shares * 5.3 / 2 == pytest.approx(5.3 / 2)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_none_untraced_and_without_a_registry(name, monkeypatch):
    untraced = types.SimpleNamespace(trace=None, state={}, window={}, out_dir="")
    assert read(name, untraced) is None
    # a commit whose program keeps no registry: nothing is loaded, nothing raises
    monkeypatch.setattr(G, "registry", lambda: None)
    traced = types.SimpleNamespace(trace={"busy_s_chip0": 1.0}, state={},
                                   window={"traced": {"statements": 1}},
                                   out_dir="/nonexistent")
    assert read(name, traced) is None
    assert G.programs_of({"join_pairs"}) is None


def test_the_registry_of_this_tree_is_found_and_its_key_reads_an_event():
    reg = G.registry()
    assert reg is not None
    assert reg.instruction_key(
        "%fusion.85 = u32[6291456]{0:T(1024)} fusion(u32[6291456]{0} %x.1), "
        "kind=kLoop") == "fusion.85 u32[6291456]"
    assert G.short_name("jit_join_pairs(8114710924276274526)") == \
        "jit_join_pairs.274526"
    assert G.short_name("") == "no_module"

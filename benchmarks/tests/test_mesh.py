"""The mesh cell's arithmetic on hand-made event lists: collective time in
flight and its exposed part, busiest chip over the mean, the bytes that leave a
chip and their floor on the interconnect; and the readers on a run of a commit
whose program keeps no exchange counter."""

import types

import pytest

from benchmarks.harness import mesh, trace as T
from benchmarks.harness.byname import load_module
from benchmarks.harness.peaks import peaks_for

import os

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py"))


def synchronous():
    # chip 0: fusion 1-3; all-to-all 3-5 with a copy inside it 4-4.5; fusion
    # 5-6; a `while` 6-9 holding an all-gather 6.5-7.5 and a fusion 7.5-9.
    # chip 1: busy 1-5.  Window 0-10.
    return {
        "devices": [
            [("%fusion.1 = u32[8]{0} fusion(u32[8]{0} p)", 1.0, 3.0),
             ("%all-to-all.4 = u32[8]{0} all-to-all(u32[8]{0} x)", 3.0, 5.0),
             ("%copy.2 = u32[8]{0} copy(u32[8]{0} y)", 4.0, 4.5),
             ("%fusion.2 = u32[8]{0} fusion(u32[8]{0} q)", 5.0, 6.0),
             ("%while.7 = (u32[8]{0}) while((u32[8]{0}) t)", 6.0, 9.0),
             ("%all-gather.3 = u32[32]{0} all-gather(u32[8]{0} z)", 6.5, 7.5),
             ("%fusion.5 = u32[8]{0} fusion(u32[8]{0} r)", 7.5, 9.0),
             ("late", 11.0, 12.0)],
            [("fusion.9", 1.0, 5.0)],
        ],
        "modules": [[], []],
        "host": [(T.WINDOW, 0.0, 10.0)],
    }


def asynchronous():
    # all-gather-start 1-1.1, two fusions 1.1-2 and 2.5-3 while it is in
    # flight, all-gather-done 3-3.4 naming the start; a second pair, 5-5.2 and
    # 5.2-6, with nothing between.
    return {
        "devices": [[
            ("%all-gather-start.1 = (u32[8], u32[32]) all-gather-start("
             "u32[8]{0} a)", 1.0, 1.1),
            ("%fusion.1 = u32[8]{0} fusion(u32[8]{0} p)", 1.1, 2.0),
            ("%fusion.2 = u32[8]{0} fusion(u32[8]{0} q)", 2.5, 3.0),
            ("%all-gather-done.1 = u32[32]{0} all-gather-done((u32[8], "
             "u32[32]) %all-gather-start.1)", 3.0, 3.4),
            ("%all-gather-start.2 = (u32[8], u32[32]) all-gather-start("
             "u32[8]{0} b)", 5.0, 5.2),
            ("%all-gather-done.2 = u32[32]{0} all-gather-done((u32[8], "
             "u32[32]) %all-gather-start.2)", 5.2, 6.0)]],
        "modules": [[]],
        "host": [(T.WINDOW, 0.0, 10.0)],
    }


def test_a_synchronous_collective_is_exposed_for_its_self_time():
    got = mesh.reduce_mesh(synchronous())
    # in flight: 3-5 and 6.5-7.5; the copy inside the all-to-all is compute
    assert got["collective_in_flight_s"] == pytest.approx(3.0)
    assert got["collective_exposed_s"] == pytest.approx(2.5)
    # the accepted collective metric reads the same self time
    assert T.reduce_trace(synchronous())["collective_s_chip0"] == \
        pytest.approx(2.5)


def test_an_asynchronous_collective_is_exposed_where_nothing_else_runs():
    got = mesh.reduce_mesh(asynchronous())
    # in flight 1-3.4 and 5-6; fusions cover 1.1-2 and 2.5-3 of the first
    assert got["collective_in_flight_s"] == pytest.approx(2.4 + 1.0)
    assert got["collective_exposed_s"] == pytest.approx(2.4 - 0.9 - 0.5 + 1.0)


def test_a_collective_is_told_by_its_opcode_not_its_instructions_name():
    # what JAX's all_to_all and all_gather look like in a v5e's trace
    a2a = ("%all_to_all.105 = u32[4,1,1048576]{2,1,0:T(1,128)S(1)} "
           "all-to-all(%bitcast.141), channel_id=1, replica_groups={{0,1,2,3}}")
    gather = ("%all-gather.16 = pred[262144]{0:T(1024)(128)(4,1)S(1)} "
              "all-gather(%copy-done.45), channel_id=3")
    user = "%fusion.3 = s32[8]{0} fusion(%all-gather.16, %all_to_all.105)"
    tup = ("%all-gather-start.2 = (u32[8], u32[32]) all-gather-start("
           "u32[8]{0} %b)")
    assert mesh.collective_of(a2a) == "all-to-all"
    assert mesh.collective_of(gather) == "all-gather"
    assert mesh.collective_of(user) is None
    assert mesh.collective_of(tup) == "all-gather-start"
    assert mesh.collective_of("all-reduce.7") == "all-reduce"
    assert mesh.collective_of("fusion.9") is None
    # the accepted reduction goes by the instruction's name and misses it
    assert not T.COLLECTIVE.search(T.op_name(a2a))
    tr = {"devices": [[(a2a, 1.0, 2.0), (gather, 2.0, 2.5)]],
          "modules": [[]], "host": [(T.WINDOW, 0.0, 3.0)]}
    assert mesh.reduce_mesh(tr)["collective_in_flight_s"] == pytest.approx(1.5)
    assert T.reduce_trace(tr)["collective_s_chip0"] == pytest.approx(0.5)


def test_a_done_that_names_no_start_pairs_with_the_oldest_of_its_kind():
    ev = [("all-gather-start.7", 1.0, 1.1), ("all-to-all-start.2", 1.2, 1.3),
          ("all-gather-done.9", 2.0, 2.5), ("all-to-all-done.3", 3.0, 3.5)]
    assert sorted(mesh.in_flight(ev)) == [(1.0, 2.5), (1.2, 3.5)]


def test_leaves_are_the_events_that_hold_no_other():
    ev = [("while", 0.0, 10.0), ("a", 1.0, 4.0), ("inner", 2.0, 3.0),
          ("b", 4.0, 9.0), ("after", 10.0, 11.0)]
    assert [n for n, _, _ in mesh.leaves(ev)] == ["inner", "b", "after"]


def test_busiest_chip_over_the_mean():
    got = mesh.reduce_mesh(synchronous())
    assert got["busy_s"] == pytest.approx([8.0, 4.0])
    assert mesh.busy_max_over_mean(got["busy_s"]) == pytest.approx(8.0 / 6.0)
    assert mesh.busy_max_over_mean([2.0, 2.0, 2.0, 2.0]) == pytest.approx(1.0)
    assert mesh.busy_max_over_mean([0.0, 0.0]) is None
    assert mesh.reduce_mesh({"devices": [[]], "modules": [[]],
                             "host": [(T.WINDOW, 0.0, 1.0)]}) is None


def test_bytes_that_leave_a_chip_and_their_floor():
    stats = {"statements": 5, "all_to_all_bytes": 4 * 1024 * 17 * 4,
             "all_gather_bytes": 4 * 256 * 9 * 4}
    per_stmt = mesh.exchange_bytes_per_stmt(stats, 4)
    assert per_stmt == 4 * 1024 * 17 + 4 * 256 * 9
    # of four blocks one is the chip's own
    assert mesh.ici_bytes_out(per_stmt, 4) == 3 * 1024 * 17 + 3 * 256 * 9
    peak = peaks_for("TPU v5 lite")["ici_bits_per_s"]
    assert mesh.ici_floor_s(200e9, peak) == pytest.approx(1.0)


def run_like(trace=None, traced=2, attempted=6, warm=1):
    return types.SimpleNamespace(
        trace=trace, state={}, out_dir="/nonexistent",
        device={"kind": "TPU v5 lite"},
        traffic={"warm_executions": warm, "statements": [{"name": "q3"}]},
        window={"attempted": attempted, "latencies_s": {},
                "traced": {"statements": traced}})


def test_the_floor_share_divides_by_the_time_a_collective_is_in_flight(
        monkeypatch):
    stats = {"statements": 8, "all_to_all_bytes": 7 * 100e9,
             "all_gather_bytes": 0, "slots_offered": 1000, "live_rows": 250}
    monkeypatch.setattr(mesh, "exchange_stats", lambda: dict(stats))
    run = run_like(trace={"collective_s_chip0": 1.0, "chips": 4})
    run.state["mesh"] = {"busy_s": [5.0] * 4, "collective_in_flight_s": 4.0,
                         "collective_exposed_s": 3.0}
    # 7 statements sent (6 + 1 warm): 100 GB a statement, 75 GB leave, two
    # traced statements: 150 GB over 200 GB/s = 0.75 s of 4.0 s
    assert reader("mpp_ici_floor_share").read(run) == pytest.approx(18.75)
    assert reader("mpp_exchange_bytes_per_stmt").read(run) == \
        pytest.approx(100e9)
    assert reader("mpp_exchange_fill_share").read(run) == pytest.approx(25.0)


@pytest.mark.parametrize("name", [
    "mpp_collective_s_per_stmt", "mpp_collective_exposed_s_per_stmt",
    "mpp_exchange_bytes_per_stmt", "mpp_exchange_fill_share",
    "mpp_chip_busy_max_over_mean", "mpp_ici_floor_share"])
def test_every_reader_returns_none_on_a_commit_without_the_counter(
        monkeypatch, name):
    from galaxysql_tpu.parallel import mpp
    monkeypatch.delattr(mpp, "EXCHANGE_STATS")
    assert mesh.exchange_stats() is None
    untraced = run_like(trace=None)
    assert reader(name).read(untraced) is None
    if reader(name).SOURCE == "program_counter" or name == "mpp_ici_floor_share":
        traced = run_like(trace={"collective_s_chip0": 1.0, "chips": 4})
        traced.state["mesh"] = {"busy_s": [1.0] * 4,
                                "collective_in_flight_s": 1.0,
                                "collective_exposed_s": 1.0}
        assert reader(name).read(traced) is None


def test_no_statement_on_the_mesh_reads_as_no_counter(monkeypatch):
    from galaxysql_tpu.parallel import mpp
    monkeypatch.setattr(mpp, "EXCHANGE_STATS",
                        dict(mpp.EXCHANGE_STATS, statements=0))
    assert mesh.exchange_stats() is None

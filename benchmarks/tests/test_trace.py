"""The reduction from a profiler trace to busy time, top operations,
collective time and labelled idle gaps, on a synthetic event list; and the
xplane loader on a trace recorded here (the CPU backend has no device plane,
so that part shows the annotations and the host plane are read)."""

import os

import pytest

from benchmarks.harness import trace as T


def synthetic():
    # chip 0: ops at 1-2, 2-3 (fusion.1 twice), 5-6 (all-to-all); window 0-10
    # chip 1: busy 1-9
    return {
        "devices": [
            [("%fusion.1 = u32[8]{0} fusion(u32[8]{0} p)", 1.0, 2.0),
             ("%fusion.1 = u32[8]{0} fusion(u32[8]{0} p)", 2.0, 3.0),
             ("%all-to-all.4 = u32[8]{0} all-to-all(u32[8]{0} x)", 5.0, 6.0),
             ("%copy.2 = u32[8]{0} copy(u32[8]{0} y)", 5.5, 5.75),
             ("late", 11.0, 12.0)],
            [("fusion.9", 1.0, 9.0)],
        ],
        "modules": [[("jit_run(123)", 0.9, 3.1), ("jit_mpp(77)", 4.9, 6.1)], []],
        "host": [(T.WINDOW, 0.0, 10.0),
                 (T.STATEMENT + "q3", 0.0, 4.0), (T.STATEMENT + "q5", 4.0, 10.0),
                 ("np.asarray(jax.Array)", 3.0, 4.9),
                 ("PjitFunction(run)", 0.5, 0.9)],
    }


def test_busy_idle_and_window():
    r = T.reduce_trace(synthetic())
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s_chip0"] == pytest.approx(3.0)      # overlap counted once
    assert r["busy_s"] == pytest.approx((3.0 + 8.0) / 2)  # mean over chips
    assert r["chips"] == 2


def test_top_operations_carry_their_module():
    r = T.reduce_trace(synthetic())
    ops = dict(r["device_ops"])
    assert ops["jit_run.123/fusion.1"] == pytest.approx(2.0)
    # the copy runs inside the all-to-all: self times add up to the busy time
    assert ops["jit_mpp.77/all-to-all.4"] == pytest.approx(0.75)
    assert ops["jit_mpp.77/copy.2"] == pytest.approx(0.25)
    assert "late" not in ops                             # outside the window
    assert r["device_ops"][0][0] == "jit_run.123/fusion.1"
    assert sum(ops.values()) == pytest.approx(r["busy_s_chip0"])
    assert r["collective_s_chip0"] == pytest.approx(0.75)


def test_self_time_of_nested_operations():
    ev = [("while", 0.0, 10.0), ("body.a", 1.0, 4.0), ("inner", 2.0, 3.0),
          ("body.b", 4.0, 9.0), ("after", 10.0, 11.0)]
    got = {n: t for n, _, t in T.self_times(ev)}
    assert got == pytest.approx({"while": 2.0, "body.a": 2.0, "inner": 1.0,
                                 "body.b": 5.0, "after": 1.0})


def test_idle_gaps_are_labelled_with_statement_and_host_work():
    r = T.reduce_trace(synthetic())
    gaps = r["idle_gaps"]
    assert gaps[0][1] == pytest.approx(4.0)              # 6..10, nothing on host
    assert gaps[0][0].startswith("q5_at_2.000s")
    assert gaps[1][1] == pytest.approx(2.0)              # 3..5
    assert gaps[1][0] == "q3_at_3.000s_host:np.asarray(jax.Array)"
    assert all(" " not in g[0] for g in gaps)


def test_in_flight_label_without_statement_annotations():
    t = synthetic()
    t["host"] = [e for e in t["host"] if not e[0].startswith(T.STATEMENT)]
    r = T.reduce_trace(t, in_flight="64_x_point_select_in_flight")
    assert r["idle_gaps"][0][0].startswith("64_x_point_select_in_flight")


def test_window_falls_back_to_the_device_operations():
    t = synthetic()
    t["host"] = []
    assert T.window_of(t) == (1.0, 12.0)


def test_a_trace_with_no_device_operation_is_refused():
    with pytest.raises(ValueError):
        T.reduce_trace({"devices": [[]], "modules": [[]],
                        "host": [(T.WINDOW, 0.0, 1.0)]})


def test_loader_reads_a_recorded_trace(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(T.WINDOW):
        with jax.profiler.TraceAnnotation(T.STATEMENT + "q1"):
            jnp.arange(1000).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = T.newest_xplane(str(tmp_path))
    assert os.path.getsize(path) > 0
    loaded = T.load_xplane(path)
    names = {e[0] for e in loaded["host"]}
    assert T.WINDOW in names and T.STATEMENT + "q1" in names
    lo, hi = T.window_of(loaded)
    assert hi > lo
    if jax.devices()[0].platform != "tpu":
        assert loaded["devices"] == []

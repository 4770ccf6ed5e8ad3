"""The bulk path (`TableStore.insert_arrays`): a string column handed over
pre-encoded (`EncodedStrings`) against the same values as strings, NULLs and an
empty table, the path's counters and span worked out by hand, ANALYZE's
sketches against the forms they replaced, and the device cache's evictions."""

from __future__ import annotations

import numpy as np
import pytest

from galaxysql_tpu.chunk.batch import EncodedStrings
from galaxysql_tpu.exec.device_cache import DeviceCache
from galaxysql_tpu.meta import statistics
from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.storage import table_store
from galaxysql_tpu.utils import tracing
from galaxysql_tpu.utils.metrics import MetricsRegistry

DDL = """CREATE TABLE {name} (
    id INT NOT NULL PRIMARY KEY, mode VARCHAR(10), note VARCHAR(20),
    price DECIMAL(15,2) NOT NULL, day DATE NOT NULL
) PARTITION BY HASH(id) PARTITIONS 4"""
MODES = ["TRUCK", "AIR", "REG AIR", "MAIL", "unused"]


def columns(n: int, seed: int, nulls: bool):
    """(the columns with `mode` and `note` as strings, the same pre-encoded)."""
    rng = np.random.default_rng(seed)
    mode = rng.integers(0, 4, n)          # "unused" never occurs
    note = rng.integers(0, 50, n)
    if nulls:
        mode[rng.random(n) < 0.2] = -1
    notes = np.array([f"note {k % 7} {k}" for k in range(50)])
    base = {"id": np.arange(n) * 3 + 1,
            "price": np.round(rng.uniform(1, 1000, n), 2),
            "day": 8035 + rng.integers(0, 2000, n)}
    strings = dict(base, mode=[None if c < 0 else MODES[c] for c in mode],
                   note=notes[note])
    encoded = dict(base, mode=EncodedStrings(mode.astype(np.int8), MODES),
                   note=EncodedStrings(note.astype(np.int16), notes))
    return strings, encoded


@pytest.fixture
def session():
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE b")
    s.execute("USE b")
    return s


def loaded(s, name: str, data):
    s.execute(DDL.format(name=name))
    store = s.instance.store("b", name)
    assert store.insert_arrays(data, s.instance.tso.next_timestamp()) == \
        len(data["id"])
    return store


def stats_of(tm):
    st = tm.stats
    return {"rows": st.row_count, "ndv": dict(st.ndv), "min_max": dict(st.min_max),
            "registers": {k: v.registers.tolist() for k, v in st.sketches.items()},
            "heavy": {k: (list(v.counts.items()), v.total)
                      for k, v in st.heavy.items()},
            "histograms": {k: None if h is None else
                           (h.bounds.tolist(), h.total, h.ndv)
                           for k, h in st.histograms.items()}}


@pytest.mark.parametrize("nulls", [False, True], ids=["not_null", "nulls"])
def test_a_pre_encoded_column_loads_as_its_strings_do(session, nulls):
    strings, encoded = columns(5000, 11, nulls)
    a, b = loaded(session, "a", strings), loaded(session, "b", encoded)
    assert len(a.partitions) == len(b.partitions) == 4
    for pa, pb in zip(a.partitions, b.partitions):
        assert pa.num_rows == pb.num_rows > 0
        for c in a.table.columns:
            assert pa.lanes[c.name].dtype == pb.lanes[c.name].dtype
            assert np.array_equal(pa.lanes[c.name], pb.lanes[c.name]), c.name
            assert np.array_equal(pa.valid[c.name], pb.valid[c.name]), c.name
    for name in ("mode", "note"):
        assert a.table.dictionaries[name].values == b.table.dictionaries[name].values
    assert "unused" not in b.table.dictionaries["mode"].values
    # dictionary codes follow the sorted distinct values, as np.unique gave them
    assert a.table.dictionaries["mode"].values == sorted(MODES[:4])
    query = "SELECT id, mode, note, price, day FROM {} ORDER BY id"
    rows = session.execute(query.format("a")).rows
    assert rows == session.execute(query.format("b")).rows
    assert [r[1] for r in rows] == strings["mode"]
    assert [r[2] for r in rows] == strings["note"].tolist()
    session.execute("ANALYZE TABLE a, b")
    assert stats_of(a.table) == stats_of(b.table)
    null_rows = session.execute("SELECT COUNT(*) FROM b WHERE mode IS NULL").rows
    assert null_rows[0][0] == strings["mode"].count(None)
    assert (null_rows[0][0] > 0) == nulls


def test_an_empty_table_loads_nothing(session):
    strings, encoded = columns(0, 1, False)
    for name, data in (("a", strings), ("b", encoded)):
        store = loaded(session, name, data)
        assert [p.num_rows for p in store.partitions] == [0, 0, 0, 0]
        assert session.execute(f"SELECT COUNT(*) FROM {name}").rows == [(0,)]
        session.execute(f"ANALYZE TABLE {name}")
        assert store.table.stats.row_count == 0


def test_a_second_load_appends_to_the_first(session):
    _, first = columns(1000, 3, True)
    _, second = columns(500, 4, False)
    second["id"] = second["id"] + 1            # other keys
    store = loaded(session, "b", first)
    held = [p.lanes["id"] for p in store.partitions]
    store.insert_arrays(second, session.instance.tso.next_timestamp())
    assert sum(p.num_rows for p in store.partitions) == 1500
    for p, before in zip(store.partitions, held):
        assert np.array_equal(p.lanes["id"][:len(before)], before)
    got = session.execute("SELECT id FROM b ORDER BY id").rows
    assert [r[0] for r in got] == sorted(first["id"].tolist() + second["id"].tolist())


def test_load_stats_and_the_load_span_by_hand(session):
    _, data = columns(1000, 5, False)
    data["mode"] = np.asarray(data["mode"]).tolist()      # one of two pre-encoded
    session.execute(DDL.format(name="b"))
    store = session.instance.store("b", "b")
    before = dict(table_store.LOAD_STATS)
    tc = tracing.TraceContext(77)
    with tracing.activate(tc):
        store.insert_arrays(data, session.instance.tso.next_timestamp())
    grew = {k: table_store.LOAD_STATS[k] - before[k] for k in before}
    # id, mode, note, day: 4-byte lanes; price: 8; a validity byte a column;
    # two 8-byte timestamp lanes
    by_hand = 1000 * (4 * 4 + 8 + 5 + 16)
    assert (grew["calls"], grew["rows"], grew["bytes"]) == (1, 1000, by_hand)
    assert all(grew[k] > 0 for k in ("encode_s", "route_s", "append_s"))
    [span] = [sp for sp in tc.spans if sp.name == "load:b"]
    assert span.kind == "load" and span.dur_us > 0
    assert span.attrs == {"rows": 1000, "encoded": "1/2", "bytes": by_hand}


def test_analyze_leaves_a_span_a_table(session):
    _, data = columns(300, 6, False)
    loaded(session, "b", data)
    session.execute("SET ENABLE_QUERY_TRACING = 1")
    session.execute("ANALYZE TABLE b")
    [span] = [sp for sp in session.last_spans if sp.name == "analyze:b"]
    assert span.kind == "analyze" and span.attrs["rows"] == 300
    assert span.dur_us > 0


def loop_registers(values: np.ndarray) -> np.ndarray:
    """HyperLogLog's registers as the sketch filled them before: a pass a bit
    for the rank, `np.maximum.at` for the registers."""
    sk = statistics.NdvSketch
    h = statistics._mix64(values.astype(np.int64).astype(np.uint64))
    idx = (h >> np.uint64(64 - sk.P)).astype(np.int64)
    rest = h << np.uint64(sk.P)
    lz = np.full(h.shape, 64 - sk.P + 1, dtype=np.uint8)
    found = np.zeros(h.shape, dtype=bool)
    for bit in range(64 - sk.P):
        is_set = ~found & (((rest >> np.uint64(63 - bit)) & np.uint64(1)) == 1)
        lz[is_set] = bit + 1
        found |= is_set
    registers = np.zeros(sk.M, dtype=np.uint8)
    np.maximum.at(registers, idx, lz)
    return registers


LANES = {
    "codes": lambda rng: rng.integers(0, 7, 20000).astype(np.int32),
    "dates": lambda rng: (8035 + rng.integers(0, 2500, 20000)).astype(np.int32),
    "sparse_keys": lambda rng: rng.integers(0, 1 << 40, 20000) * 4 - 3,
    "negative": lambda rng: rng.integers(-500, 500, 20000),
    "one_value": lambda rng: np.full(1000, 42, np.int64),
    "zero_low_bits": lambda rng: np.arange(1 << 12, dtype=np.int64) << 52,
    "floats": lambda rng: np.round(rng.uniform(0, 10, 5000), 1),
}


@pytest.mark.parametrize("lane", list(LANES))
def test_value_counts_and_the_sketches_equal_the_forms_they_replaced(lane):
    values = LANES[lane](np.random.default_rng(9))
    vals, counts = statistics.value_counts(values)
    want_vals, want_counts = np.unique(values, return_counts=True)
    assert vals.dtype == want_vals.dtype and counts.dtype == want_counts.dtype
    assert np.array_equal(vals, want_vals) and np.array_equal(counts, want_counts)
    if values.dtype.kind != "f":
        sk = statistics.NdvSketch()
        sk.add_array(values[:len(values) // 2])
        sk.add_array(values[len(values) // 2:])
        assert np.array_equal(sk.registers, loop_registers(values))
        distinct = statistics.NdvSketch()
        distinct.add_array(vals)        # what ANALYZE hands the sketch
        assert np.array_equal(distinct.registers, sk.registers)
    hh, by_counts = statistics.HeavyHitterSketch(), statistics.HeavyHitterSketch()
    hh.add_array(values)
    by_counts.add_counts(want_vals, want_counts)
    assert (hh.counts, hh.total) == (by_counts.counts, by_counts.total)
    assert hh.total == len(values)


class FakeStore:
    def __init__(self, uid):
        self.uid = uid


def test_the_device_cache_evicts_past_its_budget_and_counts_it():
    cache = DeviceCache(budget_bytes=1000)
    registry = MetricsRegistry()
    cache.bind_metrics(registry)
    store = FakeStore(10_000_001)
    lanes = [np.full(100, i, np.int32) for i in range(4)]     # 400 bytes each
    for i, lane in enumerate(lanes[:2]):
        cache.get_lane(store, 0, f"c{i}", 1, lane)
    assert (cache.evictions, cache.evicted_bytes, cache._bytes) == (0, 0, 800)
    cache.get_lane(store, 0, "c2", 1, lanes[2])     # 1,200 > 1,000: c0 goes
    assert (cache.evictions, cache.evicted_bytes, cache._bytes) == (1, 400, 800)
    cache.get_lane(store, 0, "c1", 1, lanes[1])     # a hit refreshes c1
    cache.get_lane(store, 0, "c3", 1, lanes[3])     # so c2 is the oldest
    assert (cache.evictions, cache.evicted_bytes, cache._bytes) == (2, 800, 800)
    assert cache.misses == 4 and cache.hits == 1
    again = cache.get_lane(store, 0, "c0", 1, lanes[0])   # uploaded anew
    assert np.asarray(again).tolist() == lanes[0].tolist()
    assert (cache.evictions, cache.misses) == (3, 5)
    gauges = {m.name: m.value for m in registry._metrics.values()}
    assert gauges["device_cache_evictions"] == 3
    assert gauges["device_cache_bytes"] == 800

"""The sysbench kind's two forms of a row, vectorised (load) and scalar
(the load generator's check), are one function of (seed, table, id)."""

import random

from benchmarks.deployments import sysbench as S


def test_vector_and_scalar_rows_agree():
    seed, table, rows = 2147483659, 7, 3000
    cols = S.table_columns(seed, table, rows)
    tkey = S.table_key(seed, table)
    for i in (1, 2, 999, 3000):
        assert cols["c"][i - 1] == S.c_value(tkey, i)
        assert cols["pad"][i - 1] == S.pad_value(tkey, i)
        assert cols["k"][i - 1] == S.k_value(tkey, i, rows)
        assert cols["id"][i - 1] == i


def test_row_shape_is_sysbenchs():
    c = S.c_value(S.table_key(1, 1), 5)
    pad = S.pad_value(S.table_key(1, 1), 5)
    assert len(c) == 119 and len(c.split("-")) == 10
    assert len(pad) == 59 and len(pad.split("-")) == 5
    assert all(len(g) == 11 and g.isdigit() for g in c.split("-"))


def test_operation_is_a_point_select_with_its_expected_reply():
    params = {"tables": 4, "rows_per_table": 100,
              "table_keys": [S.table_key(9, t) for t in range(1, 5)]}
    sql, expected = S.operation(params, random.Random(3))
    table = int(sql.split("sbtest")[1].split()[0])
    row_id = int(sql.split("id=")[1])
    assert sql == f"SELECT c FROM sbtest{table} WHERE id={row_id}"
    assert expected == [(S.c_value(params["table_keys"][table - 1], row_id),)]


def test_seeds_and_tables_give_different_rows():
    a = S.c_value(S.table_key(1, 1), 1)
    assert a != S.c_value(S.table_key(2, 1), 1)
    assert a != S.c_value(S.table_key(1, 2), 1)

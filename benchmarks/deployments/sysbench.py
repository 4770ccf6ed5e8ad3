"""Deployment kind `sysbench`: sysbench 1.0 `oltp_common.lua` tables
(`sbtest<N>(id, k, c CHAR(120), pad CHAR(60))`) and the `oltp_point_select`
statement, `SELECT c FROM sbtest<N> WHERE id=?`.

Row contents are a pure function of (seed, table, id) in sysbench's own
format (`c`: ten groups of eleven digits joined by '-', `pad`: five), so the
load generator works out every expected reply without holding the table.
The vectorised form loads the rows, the scalar form checks them; a test holds
the two equal.  Nothing of the program (and no JAX) is imported before `load`
is called, and the generator processes never call it."""

from __future__ import annotations

import time

import numpy as np

M64 = (1 << 64) - 1
GROUP = 10 ** 11
C_GROUPS, PAD_GROUPS = 10, 5


def mix64(x: int) -> int:
    """splitmix64's finaliser on Python integers."""
    x = (x + 0x9E3779B97F4A7C15) & M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & M64
    return x ^ (x >> 31)


def mix64_np(x: np.ndarray) -> np.ndarray:
    """The same on uint64 arrays (which wrap modulo 2^64)."""
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def table_key(seed: int, table: int) -> int:
    return mix64((seed & M64) * 1000003 + table)


def group_value(tkey: int, row_id: int, g: int) -> int:
    return mix64(tkey ^ ((row_id * 16 + g) & M64)) % GROUP


def c_value(tkey: int, row_id: int) -> str:
    return "-".join(f"{group_value(tkey, row_id, g):011d}"
                    for g in range(C_GROUPS))


def pad_value(tkey: int, row_id: int) -> str:
    return "-".join(f"{group_value(tkey, row_id, C_GROUPS + g):011d}"
                    for g in range(PAD_GROUPS))


def k_value(tkey: int, row_id: int, rows: int) -> int:
    return 1 + group_value(tkey, row_id, 15) % rows


def group_values_np(tkey: int, ids: np.ndarray, g: int) -> np.ndarray:
    """`group_value` for an array of ids."""
    return mix64_np(np.uint64(tkey) ^ (ids.astype(np.uint64) * np.uint64(16)
                                       + np.uint64(g))) % np.uint64(GROUP)


def _digit_strings(tkey: int, ids: np.ndarray, first: int, groups: int
                   ) -> np.ndarray:
    width = groups * 12 - 1
    out = np.full((len(ids), width), ord("-"), dtype=np.uint8)
    for g in range(groups):
        v = group_values_np(tkey, ids, first + g)
        for j in range(11):
            out[:, g * 12 + 10 - j] = 48 + (v % np.uint64(10)).astype(np.uint8)
            v = v // np.uint64(10)
    return out.view(f"S{width}").ravel().astype(f"U{width}")


def table_columns(seed: int, table: int, rows: int) -> dict:
    """All rows of `sbtest<table>` as column arrays."""
    tkey = table_key(seed, table)
    ids = np.arange(1, rows + 1, dtype=np.int64)
    k = 1 + (group_values_np(tkey, ids, 15) % np.uint64(rows)).astype(np.int64)
    return {"id": ids, "k": k,
            "c": _digit_strings(tkey, ids, 0, C_GROUPS),
            "pad": _digit_strings(tkey, ids, C_GROUPS, PAD_GROUPS)}


DDL = ("CREATE TABLE sbtest{n} (id INT NOT NULL PRIMARY KEY, "
       "k INT NOT NULL DEFAULT 0, c CHAR(120) NOT NULL DEFAULT '', "
       "pad CHAR(60) NOT NULL DEFAULT '', KEY k_{n} (k)) "
       "PARTITION BY HASH(id) PARTITIONS {partitions}")


# -- what a load generator process needs: no table, no program, no JAX --------

def operation(params: dict, rng) -> tuple:
    """One `oltp_point_select`: (sql, expected rows), table and id uniform."""
    table = rng.randrange(1, params["tables"] + 1)
    row_id = rng.randrange(1, params["rows_per_table"] + 1)
    sql = f"SELECT c FROM sbtest{table} WHERE id={row_id}"
    return sql, [(c_value(params["table_keys"][table - 1], row_id),)]


class Deployment:
    def __init__(self, served, config, seed, rows_per_table, timings):
        self.served = served
        self.config = config
        self.database = config["database"]
        self.seed = seed
        self.rows_per_table = rows_per_table
        self.timings = timings

    def generator_params(self) -> dict:
        tables = self.config["tables"]
        return {"tables": tables, "rows_per_table": self.rows_per_table,
                "table_keys": [table_key(self.seed, t)
                               for t in range(1, tables + 1)]}


def load(served, config, seed: int, dry_run: bool) -> Deployment:
    rows = config["dry_run_rows_per_table"] if dry_run \
        else config["rows_per_table"]
    inst = served.instance
    db = config["database"]
    t0 = time.perf_counter()
    c = served.connect()
    try:
        c.query(f"CREATE DATABASE {db}")
        c.query(f"USE {db}")
        names = []
        for t in range(1, config["tables"] + 1):
            c.query(DDL.format(n=t, partitions=config["partitions"]))
            inst.store(db, f"sbtest{t}").insert_arrays(
                table_columns(seed, t, rows), inst.tso.next_timestamp())
            names.append(f"sbtest{t}")
        c.query("ANALYZE TABLE " + ", ".join(names))
        for name in names:
            got = int(c.query(f"SELECT COUNT(*) FROM {name}")[1][0][0])
            assert got == rows, f"{name}: COUNT(*) = {got}, loaded {rows}"
    finally:
        c.close()
    return Deployment(served, config, seed, rows,
                      {"generate_s": 0.0, "load_s": time.perf_counter() - t0})

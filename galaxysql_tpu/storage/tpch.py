"""TPC-H schema, data generator, and query texts (from the public TPC-H specification).

The reference validates its planner against TPC-H plan fixtures
(`planner/tpch/MppTpchPlan100gTest.java`, SURVEY.md §4); here TPC-H is both the planner
test corpus and the benchmark workload (BASELINE.md configs).

The generator is a simplified dbgen: uniform distributions with the spec's value domains and
cardinality ratios (SF-scaled), deterministic per seed.  It is NOT word-for-word dbgen (no
text grammar); v_strings are drawn from small vocabularies, which keeps dictionaries compact
— representative for engine benchmarking, not for audited TPC-H publication.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np

from galaxysql_tpu.chunk.batch import EncodedStrings

# ---------------------------------------------------------------------------
# schema (spec §1.4) — PolarB-X-flavoured partitioned DDL
# ---------------------------------------------------------------------------

TPCH_DDL = {
    "region": """
        CREATE TABLE region (
            r_regionkey INT NOT NULL PRIMARY KEY,
            r_name      VARCHAR(25) NOT NULL,
            r_comment   VARCHAR(152)
        ) BROADCAST
    """,
    "nation": """
        CREATE TABLE nation (
            n_nationkey INT NOT NULL PRIMARY KEY,
            n_name      VARCHAR(25) NOT NULL,
            n_regionkey INT NOT NULL,
            n_comment   VARCHAR(152)
        ) BROADCAST
    """,
    "supplier": """
        CREATE TABLE supplier (
            s_suppkey   INT NOT NULL PRIMARY KEY,
            s_name      VARCHAR(25) NOT NULL,
            s_address   VARCHAR(40) NOT NULL,
            s_nationkey INT NOT NULL,
            s_phone     VARCHAR(15) NOT NULL,
            s_acctbal   DECIMAL(15,2) NOT NULL,
            s_comment   VARCHAR(101) NOT NULL
        ) PARTITION BY HASH(s_suppkey) PARTITIONS 8
    """,
    "part": """
        CREATE TABLE part (
            p_partkey     INT NOT NULL PRIMARY KEY,
            p_name        VARCHAR(55) NOT NULL,
            p_mfgr        VARCHAR(25) NOT NULL,
            p_brand       VARCHAR(10) NOT NULL,
            p_type        VARCHAR(25) NOT NULL,
            p_size        INT NOT NULL,
            p_container   VARCHAR(10) NOT NULL,
            p_retailprice DECIMAL(15,2) NOT NULL,
            p_comment     VARCHAR(23) NOT NULL
        ) PARTITION BY HASH(p_partkey) PARTITIONS 8
    """,
    "partsupp": """
        CREATE TABLE partsupp (
            ps_partkey    INT NOT NULL,
            ps_suppkey    INT NOT NULL,
            ps_availqty   INT NOT NULL,
            ps_supplycost DECIMAL(15,2) NOT NULL,
            ps_comment    VARCHAR(199) NOT NULL,
            PRIMARY KEY (ps_partkey, ps_suppkey)
        ) PARTITION BY HASH(ps_partkey) PARTITIONS 8
    """,
    "customer": """
        CREATE TABLE customer (
            c_custkey    INT NOT NULL PRIMARY KEY,
            c_name       VARCHAR(25) NOT NULL,
            c_address    VARCHAR(40) NOT NULL,
            c_nationkey  INT NOT NULL,
            c_phone      VARCHAR(15) NOT NULL,
            c_acctbal    DECIMAL(15,2) NOT NULL,
            c_mktsegment VARCHAR(10) NOT NULL,
            c_comment    VARCHAR(117) NOT NULL
        ) PARTITION BY HASH(c_custkey) PARTITIONS 8
    """,
    "orders": """
        CREATE TABLE orders (
            o_orderkey      BIGINT NOT NULL PRIMARY KEY,
            o_custkey       INT NOT NULL,
            o_orderstatus   VARCHAR(1) NOT NULL,
            o_totalprice    DECIMAL(15,2) NOT NULL,
            o_orderdate     DATE NOT NULL,
            o_orderpriority VARCHAR(15) NOT NULL,
            o_clerk         VARCHAR(15) NOT NULL,
            o_shippriority  INT NOT NULL,
            o_comment       VARCHAR(79) NOT NULL
        ) PARTITION BY HASH(o_orderkey) PARTITIONS 8
    """,
    "lineitem": """
        CREATE TABLE lineitem (
            l_orderkey      BIGINT NOT NULL,
            l_partkey       INT NOT NULL,
            l_suppkey       INT NOT NULL,
            l_linenumber    INT NOT NULL,
            l_quantity      DECIMAL(15,2) NOT NULL,
            l_extendedprice DECIMAL(15,2) NOT NULL,
            l_discount      DECIMAL(15,2) NOT NULL,
            l_tax           DECIMAL(15,2) NOT NULL,
            l_returnflag    VARCHAR(1) NOT NULL,
            l_linestatus    VARCHAR(1) NOT NULL,
            l_shipdate      DATE NOT NULL,
            l_commitdate    DATE NOT NULL,
            l_receiptdate   DATE NOT NULL,
            l_shipinstruct  VARCHAR(25) NOT NULL,
            l_shipmode      VARCHAR(10) NOT NULL,
            l_comment       VARCHAR(44) NOT NULL,
            PRIMARY KEY (l_orderkey, l_linenumber)
        ) PARTITION BY HASH(l_orderkey) PARTITIONS 8
    """,
}

TABLE_ORDER = ["region", "nation", "supplier", "part", "partsupp", "customer",
               "orders", "lineitem"]

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINERS1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINERS2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
P_NAME_WORDS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
                "blanched", "blue", "blush", "brown", "burlywood", "burnished", "chartreuse",
                "chiffon", "chocolate", "coral", "cornflower", "cornsilk", "cream", "cyan",
                "dark", "deep", "dim", "dodger", "drab", "firebrick", "floral", "forest",
                "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey", "honeydew",
                "hot", "hunter", "indian", "ivory", "khaki", "lace", "lavender", "lawn",
                "lemon", "light", "lime", "linen", "magenta", "maroon", "medium", "metallic",
                "midnight", "mint", "misty", "moccasin", "navajo", "navy", "olive", "orange",
                "orchid", "pale", "papaya", "peach", "peru", "pink", "plum", "powder",
                "puff", "purple", "red", "rose", "rosy", "royal", "saddle", "salmon",
                "sandy", "seashell", "sienna", "sky", "slate", "smoke", "snow", "spring",
                "steel", "tan", "thistle", "tomato", "turquoise", "violet", "wheat",
                "white", "yellow"]

_EPOCH_1992 = 8035   # days('1992-01-01')
_ORDER_DATE_RANGE = 2406  # through 1998-08-02

_COMMENT_WORDS = np.array(["carefully", "quickly", "furiously", "slyly", "blithely",
                           "final", "special", "pending", "regular", "express", "ironic",
                           "even", "bold", "silent", "dogged", "instructions", "requests",
                           "deposits", "packages", "accounts", "foxes", "ideas", "theodolites",
                           "pinto", "beans", "platelets", "asymptotes"])


_COMMENT_VALUES = np.array([f"{a} {b} {c}" for a in _COMMENT_WORDS
                            for b in _COMMENT_WORDS for c in _COMMENT_WORDS])


# rows a draw: what a step draws is read once and dropped, and the column keeps
# the narrow result alone.  The generator hands out the same numbers whether a
# column is drawn whole or in steps (`tests/test_tpch_generate.py`).
_STEP = 1 << 20


def _integers(rng: np.random.Generator, lo: int, hi: int, n: int, dtype) -> np.ndarray:
    """`rng.integers(lo, hi, n)` kept as `dtype`."""
    out = np.empty(n, dtype)
    for i in range(0, n, _STEP):
        out[i:i + _STEP] = rng.integers(lo, hi, min(_STEP, n - i))
    return out


def _coin(rng: np.random.Generator, n: int) -> np.ndarray:
    """`rng.random(n) < 0.5`."""
    out = np.empty(n, np.bool_)
    for i in range(0, n, _STEP):
        out[i:i + _STEP] = rng.random(min(_STEP, n - i)) < 0.5
    return out


def _comments(rng: np.random.Generator, n: int) -> EncodedStrings:
    """Three words of 27, `rng.integers(0, 27, (n, 3))` as before, kept as a
    code into the 19,683 phrases."""
    k = len(_COMMENT_WORDS)
    codes = np.empty(n, np.int16)
    for i in range(0, n, _STEP):
        w = rng.integers(0, k, (min(_STEP, n - i), 3))
        codes[i:i + _STEP] = (w[:, 0] * k + w[:, 1]) * k + w[:, 2]
    return EncodedStrings(codes, _COMMENT_VALUES)


def _pick(values: Sequence[str], codes: np.ndarray) -> EncodedStrings:
    """A small-domain string column: `codes` into `values`."""
    return EncodedStrings(codes.astype(np.int8 if len(values) < 128 else np.int16,
                                       copy=False), np.asarray(values))


def _numbered(prefix: str, keys: np.ndarray, width: int = 0) -> np.ndarray:
    """`f"{prefix}{k:0{width}d}"` for every key, as one array of strings."""
    digits = keys.astype(str)
    return np.char.add(prefix, np.char.zfill(digits, width) if width else digits)


def _phones(keys: np.ndarray) -> EncodedStrings:
    """`f"{10+k%25}-{k%900+100}-{k%9000+1000}"`: the text repeats every 9,000
    keys, so it is formatted once a residue."""
    k = np.arange(9000)
    text = np.char.add(np.char.add((10 + k % 25).astype(str), "-"),
                       np.char.add(np.char.add((k % 900 + 100).astype(str), "-"),
                                   (k % 9000 + 1000).astype(str)))
    return EncodedStrings((keys % 9000).astype(np.int16), text)


def row_counts(sf: float) -> Dict[str, int]:
    """Rows a table at scale factor `sf`; `lineitem` is drawn (1-7 lines an
    order, 4 on average) and is given as its expectation."""
    n_part = max(int(200_000 * sf), 200)
    n_cust = max(int(150_000 * sf), 150)
    return {"region": 5, "nation": 25, "supplier": max(int(10_000 * sf), 50),
            "part": n_part, "partsupp": n_part * 4, "customer": n_cust,
            "orders": n_cust * 10, "lineitem": n_cust * 40}


def generate_arrays(sf: float, seed: int = 19920101) -> Dict[str, Dict[str, Any]]:
    """All eight tables at scale factor `sf`, a column as a numpy array or, for
    a string column of a small domain, as `EncodedStrings` (codes and their
    dictionary).  Every draw comes from `default_rng(seed)` in the order it
    always did, so `(sf, seed)` names the same data as before."""
    rng = np.random.default_rng(seed)
    out: Dict[str, Dict[str, Any]] = {}
    counts = row_counts(sf)
    n_supp, n_part, n_cust = counts["supplier"], counts["part"], counts["customer"]
    n_ps, n_ord = counts["partsupp"], counts["orders"]

    out["region"] = {
        "r_regionkey": np.arange(5),
        "r_name": np.asarray(REGIONS),
        "r_comment": _comments(rng, 5),
    }
    out["nation"] = {
        "n_nationkey": np.arange(25),
        "n_name": np.asarray([n for n, _ in NATIONS]),
        "n_regionkey": np.asarray([r for _, r in NATIONS]),
        "n_comment": _comments(rng, 25),
    }

    supp_keys = np.arange(1, n_supp + 1)
    out["supplier"] = {
        "s_suppkey": supp_keys,
        "s_name": _numbered("Supplier#", supp_keys, 9),
        "s_address": _numbered("addr", supp_keys),
        "s_nationkey": rng.integers(0, 25, n_supp),
        "s_phone": _phones(supp_keys),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_supp), 2),
        "s_comment": _comments(rng, n_supp),
    }

    part_keys = np.arange(1, n_part + 1)
    name_ix = rng.integers(0, len(P_NAME_WORDS), (n_part, 5))
    mfgr = rng.integers(1, 6, n_part)
    brand = mfgr * 10 + rng.integers(1, 6, n_part)
    words = np.asarray(P_NAME_WORDS)
    p_name = words[name_ix[:, 0]]
    for j in range(1, 5):
        p_name = np.char.add(np.char.add(p_name, " "), words[name_ix[:, j]])
    type_codes = (rng.integers(0, 6, n_part) * 5 + rng.integers(0, 5, n_part)) * 5 \
        + rng.integers(0, 5, n_part)
    out["part"] = {
        "p_partkey": part_keys,
        "p_name": p_name,
        "p_mfgr": _pick([f"Manufacturer#{m}" for m in range(6)], mfgr),
        "p_brand": _pick([f"Brand#{b}" for b in range(56)], brand),
        "p_type": _pick([f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2
                         for c in TYPE_S3], type_codes),
        "p_size": rng.integers(1, 51, n_part),
        "p_container": _pick([f"{a} {b}" for a in CONTAINERS1 for b in CONTAINERS2],
                             rng.integers(0, 5, n_part) * 8
                             + rng.integers(0, 8, n_part)),
        "p_retailprice": np.round(
            900 + (part_keys % 1000) / 10 + 100 * (part_keys % 10), 2),
        "p_comment": _comments(rng, n_part),
    }

    ps_part = np.repeat(part_keys, 4)
    ps_supp = np.zeros(n_ps, dtype=np.int64)
    for j in range(4):
        ps_supp[j::4] = (ps_part[j::4] + (j * (n_supp // 4 + (ps_part[j::4] - 1)
                                               % (n_supp // 4)))) % n_supp + 1
    out["partsupp"] = {
        "ps_partkey": ps_part,
        "ps_suppkey": ps_supp,
        "ps_availqty": rng.integers(1, 10_000, n_ps),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, n_ps), 2),
        "ps_comment": _comments(rng, n_ps),
    }

    cust_keys = np.arange(1, n_cust + 1)
    out["customer"] = {
        "c_custkey": cust_keys,
        "c_name": _numbered("Customer#", cust_keys, 9),
        "c_address": _numbered("addr", cust_keys),
        "c_nationkey": rng.integers(0, 25, n_cust),
        "c_phone": _phones(cust_keys),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_cust), 2),
        "c_mktsegment": _pick(SEGMENTS, rng.integers(0, 5, n_cust)),
        "c_comment": _comments(rng, n_cust),
    }

    ord_keys = np.arange(1, n_ord + 1) * 4 - 3  # sparse keys like dbgen
    o_date = _integers(rng, 0, _ORDER_DATE_RANGE, n_ord, np.int32)
    o_date += _EPOCH_1992
    # only ~2/3 of customers have orders (spec): map to custkey % 3 != 0
    o_cust = _integers(rng, 1, n_cust + 1, n_ord, np.int32)
    o_cust -= o_cust % 3 == 0
    o_cust[o_cust == 0] = 1
    o_priority = _pick(PRIORITIES, _integers(rng, 0, 5, n_ord, np.int8))
    n_clerk = max(int(sf * 1000), 10)
    o_clerk = EncodedStrings(_integers(rng, 1, n_clerk, n_ord, np.int32),
                             _numbered("Clerk#", np.arange(n_clerk), 9))
    o_comment = _comments(rng, n_ord)

    # lineitem: 1-7 lines per order; `of_line` is a line's order, counted from 0
    lines_per = _integers(rng, 1, 8, n_ord, np.int8)
    first_line = np.cumsum(lines_per, dtype=np.int64)
    n_li = int(first_line[-1])
    first_line -= lines_per
    of_line = np.zeros(n_li, np.int32)
    of_line[first_line[1:]] = 1
    np.cumsum(of_line, out=of_line)
    li_order = of_line.astype(np.int64)
    li_order *= 4
    li_order += 1
    li_odate = o_date[of_line]
    li_lineno = np.arange(1, n_li + 1, dtype=np.int32)
    li_lineno -= first_line.astype(np.int32)[of_line]
    del of_line
    l_part = _integers(rng, 1, n_part + 1, n_li, np.int32)
    l_supp = _integers(rng, 0, 4, n_li, np.int32)
    l_supp *= n_supp // 4 + 1
    l_supp += l_part
    l_supp %= n_supp
    l_supp += 1
    qty = _integers(rng, 1, 51, n_li, np.int8)
    # 900 + (l_part % 1000) / 10 + 100 * (l_part % 10), then times qty, rounded
    eprice = (l_part % 1000) / 10
    eprice += 900
    eprice += 100 * (l_part % 10)
    eprice *= qty
    np.round(eprice, 2, out=eprice)
    ship = _integers(rng, 1, 122, n_li, np.int32)
    ship += li_odate
    commit = _integers(rng, 30, 91, n_li, np.int32)
    commit += li_odate
    del li_odate
    receipt = _integers(rng, 1, 31, n_li, np.int32)
    receipt += ship
    today = _EPOCH_1992 + 1839  # 1995-06-17 per spec currentdate
    # codes into ("A", "N", "R"): returned or accepted by a coin where the line
    # was received by today, else N; and into ("F", "O")
    rflag = np.where(_coin(rng, n_li), np.int8(2), np.int8(0))
    rflag[receipt > today] = 1
    open_line = ship > today
    discount = _integers(rng, 0, 11, n_li, np.int8) / 100
    tax = _integers(rng, 0, 9, n_li, np.int8) / 100
    out["lineitem"] = {
        "l_orderkey": li_order,
        "l_partkey": l_part,
        "l_suppkey": l_supp,
        "l_linenumber": li_lineno,
        "l_quantity": qty.astype(float),
        "l_extendedprice": eprice,
        "l_discount": np.round(discount, 2, out=discount),
        "l_tax": np.round(tax, 2, out=tax),
        "l_returnflag": _pick(["A", "N", "R"], rflag),
        "l_linestatus": _pick(["F", "O"], open_line),
        "l_shipdate": ship,
        "l_commitdate": commit,
        "l_receiptdate": receipt,
        "l_shipinstruct": _pick(SHIPINSTRUCT, _integers(rng, 0, 4, n_li, np.int8)),
        "l_shipmode": _pick(SHIPMODES, _integers(rng, 0, 7, n_li, np.int8)),
        "l_comment": _comments(rng, n_li),
    }

    # o_orderstatus: F if all of an order's lines are F, O if all are O, else P;
    # o_totalprice: its lines' prices added in line order (every order has a line)
    open_lines = np.add.reduceat(open_line, first_line, dtype=np.int8)
    o_status = np.where(open_lines == 0, np.int8(0),
                        np.where(open_lines == lines_per, np.int8(1), np.int8(2)))
    o_total = np.add.reduceat(eprice, first_line)
    out["orders"] = {
        "o_orderkey": ord_keys,
        "o_custkey": o_cust,
        "o_orderstatus": _pick(["F", "O", "P"], o_status),
        "o_totalprice": np.round(o_total, 2, out=o_total),
        "o_orderdate": o_date,
        "o_orderpriority": o_priority,
        "o_clerk": o_clerk,
        "o_shippriority": np.zeros(n_ord, dtype=np.int32),
        "o_comment": o_comment,
    }
    return {t: out[t] for t in TABLE_ORDER}


def generate(sf: float, seed: int = 19920101) -> Dict[str, Dict[str, list]]:
    """`generate_arrays` with every column as a list of Python values."""
    return {t: {c: v.tolist() for c, v in cols.items()}
            for t, cols in generate_arrays(sf, seed).items()}

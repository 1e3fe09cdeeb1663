"""Subquery planning/execution edge cases (decorrelation, NULL semantics).

Counterpart of the reference's expression_rewriter + decorrelate rule tests
(reference: planner/core/expression_rewriter_test.go,
rule_decorrelate.go). Each case here pins a semantic corner that the
TPC-H corpus alone does not exercise.
"""

from __future__ import annotations

import pytest

from tidb_tpu.session import Session, SQLError


@pytest.fixture()
def s():
    s = Session()
    s.execute("create table t (id bigint, k bigint, a bigint)")
    s.execute("create table u (k bigint, b bigint)")
    s.execute("insert into t values (1, 10, 5), (2, 20, 50), (3, 30, 7)")
    s.execute("insert into u values (10, 1), (10, 2), (20, 100)")
    return s


def test_correlated_count_zero(s):
    # count(*) over an empty correlated group is 0, not a dropped row
    rows = s.query("select id from t where "
                   "(select count(*) from u where u.k = t.k) = 0 "
                   "order by id")
    assert rows == [(3,)]


def test_correlated_count_nonzero(s):
    rows = s.query("select id from t where "
                   "(select count(*) from u where u.k = t.k) = 2")
    assert rows == [(1,)]


def test_correlated_agg_inner(s):
    # classic Q17 shape: compare against a correlated average
    rows = s.query("select id from t where "
                   "a > (select avg(b) from u where u.k = t.k) "
                   "order by id")
    assert rows == [(1,)]  # id=1: 5 > avg(1,2)=1.5; id=2: 50 < 100 false


def test_select_star_no_hidden_columns(s):
    rows = s.query("select * from t where "
                   "a > (select avg(b) from u where u.k = t.k)")
    assert rows == [(1, 10, 5)]  # exactly t's columns, no #corr leakage


def test_not_in_empty_set_with_null_lhs(s):
    s.execute("insert into t values (4, null, 1)")
    # NOT IN over an empty set is TRUE for every row, even NULL lhs
    rows = s.query("select id from t where "
                   "k not in (select k from u where b > 1000) order by id")
    assert rows == [(1,), (2,), (3,), (4,)]


def test_not_in_with_null_in_subquery(s):
    s.execute("insert into u values (null, 9)")
    # any NULL in the subquery side empties NOT IN results
    rows = s.query("select id from t where k not in (select k from u)")
    assert rows == []


def test_not_in_null_lhs_filtered(s):
    s.execute("insert into t values (4, null, 1)")
    rows = s.query("select id from t where "
                   "k not in (select k from u) order by id")
    assert rows == [(3,)]  # k=30 unmatched; NULL k is UNKNOWN -> filtered


def test_scalar_subquery_in_order_by(s):
    rows = s.query("select id from t order by a - (select min(b) from u)")
    assert rows == [(1,), (3,), (2,)]


def test_scalar_subquery_in_agg_arg(s):
    rows = s.query("select sum(a - (select min(b) from u)) from t")
    assert rows == [(59,)]  # (5-1)+(50-1)+(7-1)


def test_exists_with_ungrouped_aggregate_is_true(s):
    # an ungrouped aggregate always yields exactly one row, so EXISTS
    # is constant TRUE regardless of the WHERE (MySQL semantics)
    rows = s.query("select count(*) from t where "
                   "exists (select max(b) from u where u.k = 99)")
    assert rows == [(3,)]
    with pytest.raises(SQLError):
        s.query("select id from t where exists "
                "(select k from u group by k having count(*) > 1)")


def test_exists_uncorrelated_true(s):
    rows = s.query("select count(*) from t where exists (select * from u)")
    assert rows == [(3,)]


def test_scalar_subquery_empty_is_null(s):
    rows = s.query("select id from t where "
                   "a > (select b from u where b > 1000)")
    assert rows == []


def test_scalar_subquery_multirow_errors(s):
    with pytest.raises(Exception):
        s.query("select id from t where a > (select b from u)")


def test_in_subquery_semi_dedup(s):
    # two matching u rows must not duplicate the t row (semi join)
    rows = s.query("select id from t where k in (select k from u) "
                   "order by id")
    assert rows == [(1,), (2,)]


def test_distributed_min_max():
    """min/max partials must merge with pmin/pmax, not psum (P2 over ICI)."""
    import jax

    from sharded_client import sharded_client

    single = Session()
    single.execute(
        "create table m (g bigint not null, v bigint not null)")
    vals = [(i % 3, (i * 37) % 101 + 1) for i in range(512)]
    ins = ",".join(f"({g},{v})" for g, v in vals)
    single.execute(f"insert into m values {ins}")

    dist = Session(single.storage,
                   cop=sharded_client(single.storage, jax.devices()[:4]))
    sql = ("select g, min(v), max(v), sum(v), count(*) from m "
           "group by g order by g")
    assert dist.query(sql) == single.query(sql)


# ---- round-4 decorrelation extensions (reference: rule_decorrelate.go) ----

def test_correlated_in_subquery():
    from tidb_tpu.session import Session
    s = Session()
    s.execute("create table co (k bigint primary key, a bigint not null)")
    s.execute("create table ci (k bigint not null, b bigint not null)")
    s.execute("insert into co values (1, 10), (2, 20), (3, 30)")
    s.execute("insert into ci values (1, 10), (1, 11), (3, 99)")
    assert s.query("select k from co where a in "
                   "(select b from ci where ci.k = co.k) order by k") \
        == [(1,)]
    assert s.query("select k from co where a not in "
                   "(select b from ci where ci.k = co.k) order by k") \
        == [(2,), (3,)]
    # correlated IN with extra inner predicates
    assert s.query("select k from co where a in (select b from ci "
                   "where ci.k = co.k and ci.b > 10) order by k") == []


def test_exists_limit_and_trivial_aggregate():
    from tidb_tpu.session import Session
    s = Session()
    s.execute("create table eo (k bigint primary key)")
    s.execute("create table ei (k bigint)")
    s.execute("insert into eo values (1), (2)")
    s.execute("insert into ei values (1)")
    assert s.query("select k from eo where exists "
                   "(select 1 from ei where ei.k = eo.k limit 1) "
                   "order by k") == [(1,)]
    # ungrouped aggregate always yields one row: EXISTS is constant true
    assert s.query("select k from eo where exists "
                   "(select max(k) from ei where ei.k = eo.k) "
                   "order by k") == [(1,), (2,)]
    assert s.query("select k from eo where not exists "
                   "(select max(k) from ei where ei.k = eo.k)") == []

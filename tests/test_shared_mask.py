"""One read-only all-true visibility mask an epoch (store/table_store.py).

A snapshot that hides no base row holds its epoch's `all_visible` array
itself: nothing row-sized is allocated or scanned for it. These tests
hold the snapshot isolation that representation carries: who gets the
shared array and who a private copy, that nobody writes through it (the
array is read-only, so a consumer that tried would raise here and not
answer wrongly), the digest strings the device caches key on, and the
counter the benchmark's `store.shared_mask_share` reads.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import subprocess
import sys
import threading
from unittest import mock

import jax
import numpy as np
import pytest

from sharded_client import sharded_client
from tidb_tpu import obs
from tidb_tpu.copr import fragment as FR
from tidb_tpu.copr.client import CopClient
from tidb_tpu.kv import TOMBSTONE
from tidb_tpu.plan import PhysTableRead, PlanBuilder, optimize
from tidb_tpu.session import Session
from tidb_tpu.sql.parser import parse_one
from tidb_tpu.store import index as IX

N = 2000
AGG = "select count(*), sum(a), sum(b) from t"


def _load(n=N, cop=None):
    """A session over `t (k, a, b)` bulk-loaded with k = handle = 1..n."""
    s = Session(cop=cop or CopClient())
    s.execute("create table t (k bigint, a bigint, b bigint)")
    info = s.catalog.table("test", "t")
    store = s.storage.table_store(info.id)
    k = np.arange(1, n + 1, dtype=np.int64)
    store.bulk_load([k, k % 7, k * 3])
    return s, info, store


def _commit(s, info, store, sets=(), deletes=()):
    txn = s.storage.begin()
    for h, row in sets:
        txn.set_row(info.id, h, store.encode_row(list(row)))
    for h in deletes:
        txn.delete_row(info.id, h)
    return txn.commit()


def _dag(s, sql=AGG):
    node = optimize(PlanBuilder(s.catalog, s.current_db).build_select(
        parse_one(sql)))
    while not isinstance(node, PhysTableRead):
        node = node.children[0]
    return node.dag


def _device_agg(s, snap):
    """(count, sum(a), sum(b)) of AGG over `snap`, epoch and overlay
    batches merged, straight through the coprocessor client."""
    res = s.cop.execute(_dag(s), snap)
    assert res.engine == "device" and res.is_partial_agg
    rows = [r for c in res.chunks for r in c.to_pylist()]
    return tuple(int(sum(r[i] for r in rows)) for i in (0, 2, 4))


def _engines(s, sql):
    return {r[3] for r in s.execute("EXPLAIN ANALYZE " + sql).rows if r[3]}


def _parent_digest(mask):
    """The digest as the code before the shared mask computed it."""
    if mask.all():
        return "all"
    return hashlib.md5(np.packbits(mask).tobytes()).hexdigest()[:16]


# ---------------- who holds which array ----------------

def test_all_visible_snapshots_share_one_read_only_array():
    s, info, store = _load()
    ts = s.storage.tso.current()
    a, b = store.snapshot(ts), s.storage.begin().snapshot(info.id)
    shared = store.epoch.all_visible
    assert a.base_visible is shared and b.base_visible is shared
    assert a.all_base_visible and b.all_base_visible
    assert shared.dtype == bool and len(shared) == N and shared.all()
    assert not shared.flags.writeable
    with pytest.raises(ValueError):
        a.base_visible[3] = False
    with pytest.raises(ValueError):
        a.base_visible &= False
    with pytest.raises(ValueError):  # the flag cannot be taken back by a view
        a.base_visible[:10].fill(False)
    assert shared.all() and a.num_visible_rows == N


@pytest.mark.parametrize("op", ["update", "delete", "both"])
def test_committed_write_to_a_base_row_gives_a_private_mask(op):
    s, info, store = _load()
    before = s.storage.tso.current()
    sets = [(7, (7, 100, 21)), (1500, (1500, 5, 5))] \
        if op in ("update", "both") else []
    deletes = [40, N] if op in ("delete", "both") else []
    _commit(s, info, store, sets, deletes)
    hidden = sorted([h for h, _ in sets] + deletes)

    new = store.snapshot(s.storage.tso.current())
    assert not new.all_base_visible
    assert new.base_visible is not store.epoch.all_visible
    assert new.base_visible.flags.writeable
    assert np.flatnonzero(~new.base_visible).tolist() == \
        [h - 1 for h in hidden]
    assert sorted(new.overlay_handles.tolist()) == [h for h, _ in sets]
    assert new.num_visible_rows == N - len(deletes)
    assert new.mask_digest == _parent_digest(new.base_visible) != "all"

    old = store.snapshot(before)  # a reader from before the write
    assert old.base_visible is store.epoch.all_visible
    assert old.num_visible_rows == N and len(old.overlay_handles) == 0
    assert old.mask_digest == "all"
    for h in hidden:
        assert old.has_handle(h)
    assert [new.has_handle(h) for h in deletes] == [False] * len(deletes)
    assert store.epoch.all_visible.all()
    k = np.arange(1, N + 1)
    assert _device_agg(s, old) == (N, int((k % 7).sum()), int(3 * k.sum()))


def test_uncommitted_overlay_is_private_to_its_transaction():
    s, info, store = _load()
    txn = s.storage.begin()
    txn.delete_row(info.id, 11)
    txn.set_row(info.id, 12, store.encode_row([12, 1, 1]))
    mine = txn.snapshot(info.id)
    assert not mine.all_base_visible
    assert np.flatnonzero(~mine.base_visible).tolist() == [10, 11]
    assert mine.num_visible_rows == N - 1
    other = s.storage.begin().snapshot(info.id)
    assert other.base_visible is store.epoch.all_visible
    assert other.num_visible_rows == N
    # an overlay of NEW handles only hides nothing: shared plus overlay rows
    txn2 = s.storage.begin()
    txn2.set_row(info.id, store.alloc_handle(), store.encode_row([0, 1, 1]))
    ins = txn2.snapshot(info.id)
    assert ins.base_visible is store.epoch.all_visible
    assert ins.num_visible_rows == N + 1
    # the direct form the session layer uses
    direct = store.snapshot(s.storage.tso.current(), {5: TOMBSTONE})
    assert np.flatnonzero(~direct.base_visible).tolist() == [4]
    txn.rollback()
    txn2.rollback()
    assert store.epoch.all_visible.all()


def test_insert_only_deltas_keep_the_shared_mask_and_count_the_overlay():
    """htap's RF1 shape: new handles with no position in the epoch."""
    s, info, store = _load()
    s.execute("insert into t values " + ", ".join(
        f"({N + i}, {i % 7}, 1)" for i in range(1, 31)))
    snap = s.storage.begin().snapshot(info.id)
    assert snap.base_visible is store.epoch.all_visible
    assert len(snap.overlay_handles) == 30
    assert snap.num_visible_rows == N + 30
    k = np.arange(1, N + 1)
    want = (N + 30, int((k % 7).sum()) + sum(i % 7 for i in range(1, 31)),
            int(3 * k.sum()) + 30)
    assert _device_agg(s, snap) == want
    assert s.query(AGG) == [want]
    assert "device" in _engines(s, AGG)


def test_fold_makes_a_new_array_and_the_old_reader_finishes():
    s, info, store = _load()
    old = store.snapshot(s.storage.tso.current())
    old_epoch, old_arr = store.epoch, store.epoch.all_visible
    base = _device_agg(s, old)
    _commit(s, info, store, sets=[(9, (9, 50, 50))], deletes=[10, 11])
    s.execute("insert into t values (900001, 1, 1), (900002, 2, 2)")
    store.compact(s.storage.tso.current())
    assert store.epoch is not old_epoch
    new = store.snapshot(s.storage.tso.current())
    assert new.epoch.num_rows == N - 2 + 2 == len(new.epoch.all_visible)
    assert new.epoch.all_visible is not old_arr
    assert not new.epoch.all_visible.flags.writeable
    assert new.base_visible is new.epoch.all_visible
    assert new.num_visible_rows == N
    # the reader that held the old snapshot across the fold
    assert old.epoch is old_epoch and old.base_visible is old_arr
    assert old_arr.all() and len(old_arr) == N
    assert old.num_visible_rows == N
    assert _device_agg(s, old) == base
    assert int(old.column(1).data.sum()) == base[1]
    got = _device_agg(s, new)
    assert got == (N, base[1] - 9 % 7 - 10 % 7 - 11 % 7 + 50 + 1 + 2,
                   base[2] - 3 * (9 + 10 + 11) + 50 + 1 + 2)


def test_readers_beside_a_writer_that_updates_deletes_and_folds():
    """Every writer transaction keeps (count, sum(a), sum(b)) where they
    were, by an update pair, and a delete with an insert of the same
    values: so every snapshot, whatever its ts, must read the same
    triple, by its flags and through the device; a torn view (a base row
    hidden without its overlay row, a flag cleared in the shared array)
    reads another."""
    n = 600
    s, info, store = _load(n)
    k = np.arange(1, n + 1)
    want = (n, int((k % 7).sum()), int(3 * k.sum()))
    stop = threading.Event()
    errors: list = []
    arrays = [store.epoch.all_visible]
    reads = [0]

    def reader():
        r = Session(s.storage, cop=s.cop)
        try:
            while not stop.is_set():
                txn = r.storage.begin()
                try:
                    snap = txn.snapshot(info.id)
                    if snap.all_base_visible:
                        assert snap.base_visible.all()
                    else:
                        assert snap.base_visible.flags.writeable
                    assert snap.num_visible_rows == n, snap.num_visible_rows
                    assert _device_agg(r, snap) == want
                    assert snap.epoch.all_visible.all()
                    reads[0] += 1
                finally:
                    txn.rollback()
        except Exception as e:  # noqa: BLE001 — reported by the test
            errors.append(e)
            stop.set()

    def writer():
        w = Session(s.storage, cop=s.cop)
        rng = np.random.default_rng(30)
        try:
            for i in range(40):
                if stop.is_set():
                    break
                rows = {r[0]: r for r in w.query("select k, a, b from t")}
                p, q, d = (int(x) for x in rng.choice(
                    sorted(rows), 3, replace=False))
                w.execute("begin")
                w.execute(f"update t set a = a + 3, b = b - 2 where k = {p}")
                w.execute(f"update t set a = a - 3, b = b + 2 where k = {q}")
                w.execute(f"delete from t where k = {d}")
                w.execute(f"insert into t values ({10_000 + i}, "
                          f"{rows[d][1]}, {rows[d][2]})")
                w.execute("commit")
                if i % 8 == 7:
                    w.storage.flush()  # fold up to the oldest reader
                    arrays.append(store.epoch.all_visible)
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            stop.set()

    threads = [threading.Thread(target=reader) for _ in range(3)]
    threads.append(threading.Thread(target=writer))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not errors, errors
    assert reads[0] > 0
    s.storage.flush()
    arrays.append(store.epoch.all_visible)
    assert len({id(a) for a in arrays}) > 1  # a fold did happen
    for a in arrays:
        assert a.all() and not a.flags.writeable
    assert s.query(AGG) == [want]


# ---------------- the audited consumers meet the read-only array ----------

def _no_deltas(s, *tables):
    for t in tables:
        st = s.storage.table_store(s.catalog.table("test", t).id)
        assert not st.deltas
        snap = s.storage.begin().snapshot(st.table.id)
        assert snap.base_visible is st.epoch.all_visible
        assert not snap.base_visible.flags.writeable


def _calls(obj, name):
    """Patch obj.name with a wrapper that counts its calls."""
    real = getattr(obj, name)
    n = [0]

    def wrapper(*a, **kw):
        n[0] += 1
        return real(*a, **kw)

    return mock.patch.object(obj, name, wrapper), n


def _index_lookup(s):
    s.execute("create index ia on t (a)")
    info = s.catalog.table("test", "t")
    store = s.storage.table_store(info.id)
    snap = s.storage.begin().snapshot(info.id)
    srch = IX.IndexSearcher(store, snap, info.indices[0])
    eq = np.sort(srch.eq((3,)))
    assert eq.tolist() == [k for k in range(1, N + 1) if k % 7 == 3]
    rng = srch.range(5, None, True, True)
    assert len(rng) == sum(1 for k in range(1, N + 1) if k % 7 >= 5)
    s.execute("create index ik on t (k)")
    s.execute("analyze table t")
    for sql, want in (("select k, b from t where k = 17", [(17, 51)]),
                      ("select k from t where k between 5 and 7",
                       [(5,), (6,), (7,)])):
        assert s.query(sql) == want
        assert _engines(s, sql) == {"ranged"}
    data, valid = snap.gather(np.array([5, 6]), [0, 2])[1]
    assert data.tolist() == [15, 18] and valid.all()


def _dimension(s):
    """`d (dk, y)`: the unique build side of a fragment join on t.a."""
    s.execute("create table d (dk bigint primary key, y bigint)")
    dstore = s.storage.table_store(s.catalog.table("test", "d").id)
    dstore.bulk_load([np.arange(7, dtype=np.int64),
                      np.arange(7, dtype=np.int64) * 10])
    s.execute("analyze table t, d")
    _no_deltas(s, "t", "d")


def _host_fragment(s):
    _dimension(s)
    sql = "select y, count(*) from t, d where a = dk group by y order by y"

    def deny(cop, frag, snaps):
        raise FR._Fallback("forced-host")

    patch, n = _calls(FR, "_full_host_cols")
    with mock.patch.object(FR, "_device_fragment", deny), patch:
        got = s.query(sql)
    assert n[0] >= 2  # probe and build side
    k = np.arange(1, N + 1)
    assert got == [(10 * r, int((k % 7 == r).sum())) for r in range(7)]


def _ddl_backfill(s):
    from tidb_tpu.ddl.ddl import DDL
    patch, n = _calls(DDL, "_validate_unique_batch")
    with patch:
        s.execute("create unique index uk on t (k)")
    assert n[0] >= 1
    with pytest.raises(Exception, match="(?i)duplicate"):
        s.execute("create unique index ua on t (a)")
    assert s.execute("admin check table t").rows == []


def _index_join(s):
    from tidb_tpu.executor import engine as EN
    s.execute("create table big (id bigint, v bigint)")
    store = s.storage.table_store(s.catalog.table("test", "big").id)
    n = 300_000
    store.bulk_load([np.arange(1, n + 1), np.arange(1, n + 1) * 7])
    s.execute("create index big_id on big (id)")
    s.execute("create table small (k bigint, tag bigint)")
    s.execute("insert into small values " + ", ".join(
        f"({i * 37 + 5}, {i})" for i in range(200)))
    s.execute("analyze table big, small")
    _no_deltas(s, "big")
    sql = "select sum(big.v) from small, big where small.k = big.id"
    patch, calls = _calls(EN, "_run_index_join")
    with patch:
        got = s.query(sql)
    assert calls[0] == 1
    assert int(got[0][0]) == sum((i * 37 + 5) * 7 for i in range(200))


def _row_path(s):
    sql = "select k, b from t where a = 2 and k <= 30 order by k"
    assert s.query(sql) == [(k, 3 * k) for k in (2, 9, 16, 23, 30)]
    assert "device" in _engines(s, sql)
    # no filter: the host mask alone picks the rows (np.nonzero over it)
    assert s.query("select k from t limit 3") == [(1,), (2,), (3,)]
    assert len(s.query("select k, a from t")) == N
    top = s.query("select k, b from t order by b desc limit 3")
    assert top == [(N, 3 * N), (N - 1, 3 * N - 3), (N - 2, 3 * N - 6)]


def _mesh4(s):
    assert len(jax.devices()) >= 8, "conftest must provide 8 devices"
    _dimension(s)
    cop = sharded_client(s.storage, jax.devices()[:4])
    m = Session(s.storage, cop=cop)
    k = np.arange(1, N + 1)
    assert m.query(AGG) == [(N, int((k % 7).sum()), int(3 * k.sum()))]
    assert "device@mesh4" in _engines(m, AGG)
    rows = "select k, b from t where a = 2 and k <= 30 order by k"
    assert m.query(rows) == [(k, 3 * k) for k in (2, 9, 16, 23, 30)]
    top = "select k, b from t order by b desc limit 3"
    assert m.query(top) == [(N, 3 * N), (N - 1, 3 * N - 3),
                            (N - 2, 3 * N - 6)]
    # a join: the build side stages replicated, its mask under "all"
    join = "select y, count(*) from t, d where a = dk group by y order by y"
    assert m.query(join) == [(10 * r, int((k % 7 == r).sum()))
                             for r in range(7)]
    assert any("mesh4" in e for e in _engines(m, join))
    masks = [key for key in cop._mask_cache]
    assert masks and all("all" in key for key in masks), masks


@pytest.mark.parametrize("consumer", [
    _index_lookup, _host_fragment, _ddl_backfill, _index_join, _row_path,
    _mesh4], ids=lambda f: f.__name__.strip("_"))
def test_consumer_runs_on_the_read_only_array(consumer):
    """Each reader of `base_visible` the audit lists, once on a
    bulk-loaded table with no deltas: it meets the read-only array, so
    an in-place write would raise here."""
    s, info, store = _load()
    _no_deltas(s, "t")
    consumer(s)
    assert store.epoch.all_visible.all()
    for st in s.storage.tables.values():
        assert st.epoch.all_visible.all()
        assert not st.epoch.all_visible.flags.writeable


# ---------------- the digest ----------------

class _Counting(np.ndarray):
    """A bool array that counts the scans of it."""
    scans = 0

    def all(self, *a, **kw):
        type(self).scans += 1
        return super().all(*a, **kw)

    def sum(self, *a, **kw):
        type(self).scans += 1
        return super().sum(*a, **kw)


def test_shared_snapshot_digests_to_all_without_a_scan():
    s, info, store = _load()
    counting = np.ones(N, dtype=bool).view(_Counting)
    counting.setflags(write=False)
    store.epoch.all_visible = counting
    _Counting.scans = 0
    for _ in range(3):
        snap = s.storage.begin().snapshot(info.id)
        assert snap.base_visible is counting
        assert snap.mask_digest == "all"
        assert snap.num_visible_rows == N
        assert _device_agg(s, snap)[0] == N
    assert s.query("select k from t where a = 2 and k < 10") == [(2,), (9,)]
    assert s.query("select k from t order by b desc limit 1") == [(N,)]
    assert _Counting.scans == 0


def test_private_mask_digest_is_the_parents_string_computed_once():
    s, info, store = _load()
    _commit(s, info, store, deletes=[3, 700])
    snap = store.snapshot(s.storage.tso.current())
    flags = np.ones(N, dtype=bool)
    flags[[2, 699]] = False
    want = _parent_digest(flags)
    with mock.patch.object(hashlib, "md5", wraps=hashlib.md5) as md5:
        assert snap.mask_digest == want
        assert snap.mask_digest == want
    assert md5.call_count == 1
    again = store.snapshot(s.storage.tso.current())
    assert again.base_visible is not snap.base_visible
    assert again.mask_digest == want  # same flags, same device mask key
    # an empty epoch's mask is "all", as np.ones(0).all() said before
    s.execute("create table e (x bigint)")
    es = s.storage.table_store(s.catalog.table("test", "e").id)
    assert es.snapshot(s.storage.tso.current()).mask_digest == "all"


@pytest.mark.parametrize("tile_rows", [None, 512], ids=["single", "tiled"])
def test_second_statement_on_the_epoch_restages_nothing(tile_rows):
    cop = CopClient()
    if tile_rows:
        cop.TILE_ROWS = tile_rows
    s, info, store = _load(cop=cop)
    stmts = [AGG, "select k from t where a = 2 and k < 10",
             "select k from t order by b desc limit 2"]
    first = [s.query(q) for q in stmts]
    keys = sorted(map(repr, cop._mask_cache))
    assert keys and all("'all'" in k for k in keys)
    before = obs.DEVICE_TRANSFER_BYTES.get()
    with mock.patch.object(np, "ones", wraps=np.ones) as ones:
        assert [s.query(q) for q in stmts] == first
    assert obs.DEVICE_TRANSFER_BYTES.get() == before
    assert sorted(map(repr, cop._mask_cache)) == keys
    # and nothing the size of the table was allocated as ones on the way
    sized = [c for c in ones.call_args_list
             if c.args and np.ndim(c.args[0]) == 0 and int(c.args[0]) >= N]
    assert sized == []


# ---------------- the counter and the benchmark's metric ----------------

def _counter():
    return (obs.SNAPSHOT_MASK.get(mask="shared"),
            obs.SNAPSHOT_MASK.get(mask="private"))


def test_counter_is_rendered_at_zero_from_the_first_store_on():
    code = ("from tidb_tpu import obs\n"
            "from tidb_tpu.session import Session\n"
            "assert 'snapshot_mask_total{' not in "
            "obs.PROCESS_METRICS.render()\n"
            "s = Session()\n"
            "s.execute('create table z (x bigint)')\n"
            "print(obs.PROCESS_METRICS.render())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120, check=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert 'tidb_store_snapshot_mask_total{mask="shared"} 0' in out.stdout
    assert 'tidb_store_snapshot_mask_total{mask="private"} 0' in out.stdout


def test_counter_moves_by_one_a_snapshot_under_its_label():
    s, info, store = _load()
    ts = s.storage.tso.current()
    c0 = _counter()
    store.snapshot(ts)
    store.snapshot(ts)
    assert _counter() == (c0[0] + 2, c0[1])
    _commit(s, info, store, deletes=[4])
    store.snapshot(s.storage.tso.current())
    store.snapshot(ts)  # from before the delete: shared
    assert _counter() == (c0[0] + 3, c0[1] + 1)
    assert obs.lint_metrics([obs.PROCESS_METRICS]) == []


def test_benchmark_metric_reads_the_rendered_counter():
    """`benchmarks/layer_metrics/store.shared_mask_share.json` parses, its
    two patterns match the lines the program renders, and the ratio is
    1.0 over a read-only statement mix on a bulk-loaded table."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           "store.shared_mask_share.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    assert spec["moves"] == "analytic_geomean_ms"
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == spec["name"]]
    assert len(entry) == 1 and entry[0]["workloads"] == [
        "tpch10_light", "tpch10_heavy", "mesh_agg", "tpch10_joins"]
    assert entry[0]["layer"] == spec["layer"] == "coprocessor host side"

    def scrape():
        out = {}
        for line in obs.PROCESS_METRICS.render().splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                out[name] = float(value)
        return out

    def delta(before, after, pattern):
        rx = re.compile(pattern)
        keys = [k for k in after if rx.search(k)]
        return keys, sum(after[k] - before.get(k, 0.0) for k in keys)

    s, info, store = _load()
    before = scrape()
    for sql in (AGG, "select k from t where a = 2 and k < 10",
                "select k from t order by b desc limit 2", AGG):
        s.query(sql)
    after = scrape()
    nkeys, num = delta(before, after, spec["numerator"])
    dkeys, den = delta(before, after, spec["denominator"])
    assert nkeys == ['tidb_store_snapshot_mask_total{mask="shared"}']
    assert sorted(dkeys) == [
        'tidb_store_snapshot_mask_total{mask="private"}',
        'tidb_store_snapshot_mask_total{mask="shared"}']
    assert den >= 4 and num / den == 1.0
    # a statement that sees a hidden base row moves the other label
    s.execute("delete from t where k = 1")
    s.query(AGG)
    _, num2 = delta(after, scrape(), spec["numerator"])
    _, den2 = delta(after, scrape(), spec["denominator"])
    assert den2 > num2

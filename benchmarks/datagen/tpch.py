"""TPC-H data from a seed: copies of the program's generators (PR 23).

`generate_tpch` (the eight-table join set) is tidb_tpu/bench/tpch_data.py's,
copied; `generate_lineitem` makes LINEITEM alone as TPC-H 4.2.3 defines it,
for the scale factors at which the whole database would cost a minute of
every run's set-up. Both live here so that a later PR that changes the
program cannot change the data the yardstick measures on. Physical encodings
(decimals x100, dates as days since 1970-01-01, strings as (vocabulary, codes))
are what the program's bulk loaders (`load_lineitem`, `load_table`) take.
numpy only.
"""

from __future__ import annotations

import datetime as _dt
from typing import Optional

import numpy as np

ORDERS_PER_SF = 1_500_000
GEN_THREADS = 8
LINEITEM_FLAGS = ("A", "R", "N")   # codes of l_returnflag in the arrays
LINEITEM_STATUS = ("F", "O")       # codes of l_linestatus
LINEITEM_COMMENTS = 1499           # size of the l_comment pool (see below)


def parse_date(text: str) -> int:
    y, m, d = text.strip().split("-")
    return (_dt.date(int(y), int(m), int(d)) - _dt.date(1970, 1, 1)).days


def sparse_orderkeys(n_orders: int) -> np.ndarray:
    """O_ORDERKEY of TPC-H 4.2.3: only the first 8 of every 32 keys are
    used (the gaps are RF1's), so the keys are sparse and ascending."""
    i = np.arange(n_orders, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def generate_lineitem(sf: float, seed: int = 42) -> dict:
    """LINEITEM as TPC-H 4.2.3 defines it, all 16 columns, for the
    `int(1_500_000 * sf)` orders of that scale factor, without the other
    seven tables: an order has 1-7 lines (uniform), on the sparse ascending
    O_ORDERKEYs; L_PARTKEY uniform in 1..200000*SF; L_SUPPKEY one of the
    part's four suppliers by the spec's formula; L_QUANTITY 1..50;
    L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE(L_PARTKEY); L_DISCOUNT
    0.00..0.10; L_TAX 0.00..0.08; L_SHIPDATE = O_ORDERDATE + 1..121,
    L_COMMITDATE = O_ORDERDATE + 30..90, L_RECEIPTDATE = L_SHIPDATE + 1..30
    with O_ORDERDATE uniform in 1992-01-01..1998-08-02; L_RETURNFLAG R or A
    where the receipt is not after 1995-06-17, else N; L_LINESTATUS O where
    the shipment is after that date, else F; L_SHIPINSTRUCT and L_SHIPMODE
    from the spec's lists. The row count is 4 rows an order, 60 000 000 at
    SF10, for every seed (the spec's 59 986 052 is dbgen's own stream).
    L_COMMENT is drawn from a pool of 1499 texts of the spec's width, not
    free text: the program stores strings dictionary-coded, and no
    statement of a cell reads the column.

    Returns {"columns": {name: ndarray}, "vocab": {name: [str]}}: decimals
    x100, dates as days since 1970-01-01, string columns as codes into
    `vocab`. Every random draw has a stream of its own
    (`default_rng([seed, k])`) and the draws run on threads - numpy releases
    the GIL inside them - because every run of every cell pays this time."""
    from concurrent.futures import ThreadPoolExecutor

    n_ord = max(30, int(ORDERS_PER_SF * sf))
    n_part = max(20, int(200_000 * sf))
    S = max(4, int(10_000 * sf))
    d0, d1 = parse_date("1992-01-01"), parse_date("1998-08-02")
    cur = parse_date(CURRENT_DATE)

    def rng(k: int) -> np.random.Generator:
        return np.random.default_rng([seed, k])

    # 1-7 lines an order, uniform: every seed gets the same multiset of
    # order sizes (each of 1..7 n_ord // 7 times, the odd ones 4) in another
    # order, so every seed loads the same number of rows - 4 * n_ord - and
    # the programs compiled for one seed's shapes serve the next
    lines_per = np.full(n_ord, 4, dtype=np.int64)
    lines_per[:n_ord - n_ord % 7] = np.tile(np.arange(1, 8), n_ord // 7)
    rng(0).shuffle(lines_per)
    o_date = rng(1).integers(d0, d1 + 1, n_ord, dtype=np.int32)
    n = int(lines_per.sum())
    draws = {  # name: (low, high, dtype), one stream each
        "quantity": (1, 51, np.int64),
        "discount": (0, 11, np.int64),
        "tax": (0, 9, np.int64),
        "ship_delta": (1, 122, np.int32),
        "commit_delta": (30, 91, np.int32),
        "receipt_delta": (1, 31, np.int32),
        "ra": (0, 2, np.int8),
        "shipinstruct": (0, len(SHIP_INSTRUCT), np.int8),
        "shipmode": (0, len(SHIP_MODES), np.int8),
        "comment": (0, LINEITEM_COMMENTS, np.int16),
    }

    def draw(item):
        k, (name, (lo, hi, dt)) = item
        return name, rng(20 + k).integers(lo, hi, n, dtype=dt)

    def keys():
        return "keys", (np.repeat(sparse_orderkeys(n_ord), lines_per),
                        np.repeat(o_date, lines_per),
                        _line_numbers(lines_per))

    def parts():
        # 32-bit arithmetic: numpy's int64 floor division is seven times
        # slower, and every value here stays far under 2**31
        pk = rng(10).integers(1, n_part + 1, n, dtype=np.int32)
        i4 = rng(11).integers(0, 4, n, dtype=np.int32)
        supp = (pk + i4 * (S // 4 + (pk - 1) // S)) % S + 1
        # P_RETAILPRICE of 4.2.3, in cents
        retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
        return "parts", (pk.astype(np.int64), supp.astype(np.int64), retail)

    with ThreadPoolExecutor(GEN_THREADS) as pool:
        jobs = [pool.submit(keys), pool.submit(parts)] + [
            pool.submit(draw, it) for it in enumerate(draws.items())]
        d = dict(j.result() for j in jobs)
    orderkey, odate, linenumber = d.pop("keys")
    pk, suppkey, retail = d.pop("parts")
    shipdate = odate + d["ship_delta"]
    receiptdate = shipdate + d["receipt_delta"]
    returnflag = np.where(receiptdate <= cur, d["ra"],
                          np.int8(2)).astype(np.int8)  # 0=A 1=R 2=N
    return {
        "columns": {
            "l_orderkey": orderkey,
            "l_partkey": pk,
            "l_suppkey": suppkey,
            "l_linenumber": linenumber,
            "l_quantity": d["quantity"] * 100,
            "l_extendedprice": d["quantity"] * retail,
            "l_discount": d["discount"],
            "l_tax": d["tax"],
            "l_returnflag": returnflag,
            "l_linestatus": (shipdate > cur).astype(np.int8),  # 0=F 1=O
            "l_shipdate": shipdate,
            "l_commitdate": odate + d["commit_delta"],
            "l_receiptdate": receiptdate,
            "l_shipinstruct": d["shipinstruct"],
            "l_shipmode": d["shipmode"],
            "l_comment": d["comment"],
        },
        "vocab": {
            "l_returnflag": list(LINEITEM_FLAGS),
            "l_linestatus": list(LINEITEM_STATUS),
            "l_shipinstruct": list(SHIP_INSTRUCT),
            "l_shipmode": list(SHIP_MODES),
            "l_comment": _comment_vocab(rng(9), LINEITEM_COMMENTS, 43),
        },
    }


# ---------------------------------------------------------------------------
# vocabularies (TPC-H spec 4.2.2.13 / appendix grammar)
# ---------------------------------------------------------------------------

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]

# (name, regionkey) — spec's fixed 25 nations
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]

TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
P_TYPES = [f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2 for c in TYPE_S3]

CONT_S1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONT_S2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
P_CONTAINERS = [f"{a} {b}" for a in CONT_S1 for b in CONT_S2]

COLORS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod",
    "green", "grey", "honeydew", "hot", "indian", "ivory", "khaki",
    "lace", "lavender", "lawn", "lemon", "light", "lime", "linen",
    "magenta", "maroon", "medium", "metallic", "midnight", "mint", "misty",
    "moccasin", "navajo", "navy", "olive", "orange", "orchid", "pale",
    "papaya", "peach", "peru", "pink", "plum", "powder", "puff", "purple",
    "red", "rose", "rosy", "royal", "saddle", "salmon", "sandy", "seashell",
    "sienna", "sky", "slate", "smoke", "snow", "spring", "steel", "tan",
    "thistle", "tomato", "turquoise", "violet", "wheat", "white", "yellow",
]

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIP_INSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                 "TAKE BACK RETURN"]
SHIP_MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]

_NOISE = [
    "carefully", "quickly", "furiously", "slyly", "blithely", "ironic",
    "final", "bold", "express", "regular", "pending", "silent", "even",
    "special", "unusual", "ruthless", "idle", "busy", "daring", "quiet",
    "packages", "deposits", "requests", "accounts", "instructions",
    "theodolites", "pinto beans", "foxes", "ideas", "platelets", "asymptotes",
    "sleep", "haggle", "nag", "wake", "cajole", "boost", "detect", "engage",
    "among", "across", "above", "beneath", "along",
]

CURRENT_DATE = "1995-06-17"  # spec's fixed "current date"


def _comment_vocab(rng: np.random.Generator, n: int, width: int,
                   pattern: Optional[tuple[str, str]] = None,
                   pattern_frac: float = 0.0) -> list[str]:
    """n pseudo-random comments; pattern_frac of them embed 'A...B'."""
    out = []
    n_pat = int(round(n * pattern_frac))
    for i in range(n):
        words = [_NOISE[j] for j in rng.integers(0, len(_NOISE), 6)]
        if pattern is not None and i < n_pat:
            a, b = pattern
            words[1], words[3] = a, b
        out.append(" ".join(words)[:width])
    return out


def _phones(rng: np.random.Generator, nationkeys: np.ndarray) -> list[str]:
    """'CC-NNN-NNN-NNNN' with country code nationkey+10 (spec 4.2.2.9)."""
    a = rng.integers(100, 1000, len(nationkeys))
    b = rng.integers(100, 1000, len(nationkeys))
    c = rng.integers(1000, 10000, len(nationkeys))
    return [f"{int(k) + 10}-{x}-{y}-{z}"
            for k, x, y, z in zip(nationkeys, a, b, c)]


def tpch_sizes(sf: float) -> dict[str, int]:
    return {
        "region": 5,
        "nation": 25,
        "part": max(20, int(200_000 * sf)),
        "supplier": max(4, int(10_000 * sf)),
        "customer": max(10, int(150_000 * sf)),
        "orders": max(30, int(1_500_000 * sf)),
        # lineitem row count is derived (1..7 lines per order)
    }


def generate_tpch(sf: float, seed: int = 42) -> dict[str, dict[str, object]]:
    """All 8 tables as {table: {column: ndarray | (vocab, codes)}}.

    Numeric columns are physically encoded (decimals scaled x100, dates as
    proleptic day numbers). String columns are (vocab: list[str],
    codes: int64 ndarray) pairs ready for dictionary encoding.
    """
    rng = np.random.default_rng(seed)
    sz = tpch_sizes(sf)
    n_part, n_supp = sz["part"], sz["supplier"]
    n_cust, n_ord = sz["customer"], sz["orders"]
    out: dict[str, dict[str, object]] = {}

    # ---- region / nation ----------------------------------------------------
    out["region"] = {
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": (REGIONS, np.arange(5, dtype=np.int64)),
        "r_comment": (_comment_vocab(rng, 5, 152), np.arange(5)),
    }
    out["nation"] = {
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": ([n for n, _ in NATIONS], np.arange(25, dtype=np.int64)),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int64),
        "n_comment": (_comment_vocab(rng, 25, 152), np.arange(25)),
    }

    # ---- part ---------------------------------------------------------------
    pk = np.arange(1, n_part + 1, dtype=np.int64)
    # p_name: 5 distinct color words (spec 4.2.3); vectorized via code matrix
    name_codes = np.empty((n_part, 5), dtype=np.int64)
    for j in range(5):
        name_codes[:, j] = rng.integers(0, len(COLORS), n_part)
    colors = np.array(COLORS)
    p_names = [" ".join(row) for row in colors[name_codes]]
    mfgr = rng.integers(1, 6, n_part)
    brand = mfgr * 10 + rng.integers(1, 6, n_part)
    # spec 4.2.3: retailprice = (90000 + ((pk/10) mod 20001) + 100*(pk mod 1000))/100
    retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    out["part"] = {
        "p_partkey": pk,
        "p_name": _dedup(p_names),
        "p_mfgr": ([f"Manufacturer#{i}" for i in range(1, 6)], mfgr - 1),
        "p_brand": ([f"Brand#{m}{n}" for m in range(1, 6)
                     for n in range(1, 6)], (mfgr - 1) * 5 + (brand % 10 - 1)),
        "p_type": (P_TYPES, rng.integers(0, len(P_TYPES), n_part)),
        "p_size": rng.integers(1, 51, n_part, dtype=np.int64),
        "p_container": (P_CONTAINERS,
                        rng.integers(0, len(P_CONTAINERS), n_part)),
        "p_retailprice": retail,
        "p_comment": _vocab_codes(_comment_vocab(rng, 199, 23), rng, n_part),
    }

    # ---- supplier -----------------------------------------------------------
    sk = np.arange(1, n_supp + 1, dtype=np.int64)
    # every nation gets suppliers even at tiny SF (keeps Q7/Q11/Q20/Q21
    # non-degenerate); tail is uniform like the spec
    s_nation = np.where(sk <= 50, (sk - 1) % 25,
                        rng.integers(0, 25, n_supp, dtype=np.int64))
    # spec: 5/10000 suppliers embed "Customer ... Complaints", 5/10000
    # "Customer ... Recommends"; guarantee at least one of each at tiny SF
    s_comments = _comment_vocab(rng, n_supp, 101)
    n_special = max(1, n_supp * 5 // 10000)
    for i in range(n_special):
        s_comments[(i * 2) % n_supp] = \
            "carefully Customer silent Complaints sleep furiously"
        s_comments[(i * 2 + 1) % n_supp] = \
            "blithely Customer bold Recommends haggle slyly"
    out["supplier"] = {
        "s_suppkey": sk,
        "s_name": ([f"Supplier#{i:09d}" for i in range(1, n_supp + 1)],
                   np.arange(n_supp, dtype=np.int64)),
        "s_address": _vocab_codes(_comment_vocab(rng, 211, 40), rng, n_supp),
        "s_nationkey": s_nation,
        "s_phone": _dedup(_phones(rng, s_nation)),
        "s_acctbal": rng.integers(-99999, 999999, n_supp, dtype=np.int64),
        "s_comment": _dedup(s_comments),
    }

    # ---- partsupp -----------------------------------------------------------
    # spec formula: for i in 0..3, suppkey = (pk + i*(S/4 + (pk-1)/S)) % S + 1
    S = n_supp
    ps_pk = np.repeat(pk, 4)
    i4 = np.tile(np.arange(4, dtype=np.int64), n_part)
    ps_sk = (ps_pk + i4 * (S // 4 + (ps_pk - 1) // S)) % S + 1
    n_ps = len(ps_pk)
    out["partsupp"] = {
        "ps_partkey": ps_pk,
        "ps_suppkey": ps_sk,
        "ps_availqty": rng.integers(1, 10000, n_ps, dtype=np.int64),
        "ps_supplycost": rng.integers(100, 100001, n_ps, dtype=np.int64),
        "ps_comment": _vocab_codes(_comment_vocab(rng, 331, 199), rng, n_ps),
    }

    # ---- customer -----------------------------------------------------------
    ck = np.arange(1, n_cust + 1, dtype=np.int64)
    c_nation = np.where(ck <= 50, (ck - 1) % 25,
                        rng.integers(0, 25, n_cust, dtype=np.int64))
    out["customer"] = {
        "c_custkey": ck,
        "c_name": ([f"Customer#{i:09d}" for i in range(1, n_cust + 1)],
                   np.arange(n_cust, dtype=np.int64)),
        "c_address": _vocab_codes(_comment_vocab(rng, 223, 40), rng, n_cust),
        "c_nationkey": c_nation,
        "c_phone": _dedup(_phones(rng, c_nation)),
        "c_acctbal": rng.integers(-99999, 999999, n_cust, dtype=np.int64),
        "c_mktsegment": (SEGMENTS, rng.integers(0, 5, n_cust)),
        "c_comment": _vocab_codes(_comment_vocab(rng, 401, 117), rng, n_cust),
    }

    # ---- orders -------------------------------------------------------------
    ok = np.arange(1, n_ord + 1, dtype=np.int64)
    # spec: only customers with custkey % 3 != 0 place orders
    cust_pool = ck[ck % 3 != 0]
    o_cust = cust_pool[rng.integers(0, len(cust_pool), n_ord)]
    d0, d1 = parse_date("1992-01-01"), parse_date("1998-08-02")
    o_date = rng.integers(d0, d1 + 1, n_ord, dtype=np.int64)
    o_comments = _comment_vocab(rng, 997, 79,
                                pattern=("special", "requests"),
                                pattern_frac=0.012)
    rng.shuffle(o_comments)
    out["orders"] = {
        "o_orderkey": ok,
        "o_custkey": o_cust,
        # o_orderstatus patched below from lineitem statuses
        "o_orderstatus": None,
        "o_totalprice": None,  # patched below
        "o_orderdate": o_date,
        "o_orderpriority": (PRIORITIES, rng.integers(0, 5, n_ord)),
        "o_clerk": ([f"Clerk#{i:09d}" for i in range(1, max(2, n_ord // 1000) + 1)],
                    rng.integers(0, max(1, n_ord // 1000), n_ord)),
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": _vocab_codes(o_comments, rng, n_ord),
    }

    # ---- lineitem -----------------------------------------------------------
    lines_per = rng.integers(1, 8, n_ord)
    # ~1% "jumbo" orders: 7 lines of near-max quantity, so Q18's
    # sum(l_quantity) > 300 predicate discriminates at every scale factor
    jumbo = rng.random(n_ord) < 0.01
    lines_per[jumbo] = 7
    l_ok = np.repeat(ok, lines_per)
    l_odate = np.repeat(o_date, lines_per)
    n_li = len(l_ok)
    l_ln = _line_numbers(lines_per)
    l_pk = rng.integers(1, n_part + 1, n_li, dtype=np.int64)
    # pick one of the part's 4 partsupp suppliers (keeps Q9/Q20 joins alive)
    li_i4 = rng.integers(0, 4, n_li, dtype=np.int64)
    l_sk = (l_pk + li_i4 * (S // 4 + (l_pk - 1) // S)) % S + 1
    qty = rng.integers(1, 51, n_li, dtype=np.int64)
    l_jumbo = np.repeat(jumbo, lines_per)
    qty[l_jumbo] = rng.integers(45, 51, int(l_jumbo.sum()))
    l_price = qty * retail[l_pk - 1]  # retailprice is scaled x100 already
    disc = rng.integers(0, 11, n_li, dtype=np.int64)
    tax = rng.integers(0, 9, n_li, dtype=np.int64)
    ship = l_odate + rng.integers(1, 122, n_li)
    commit = l_odate + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    cur = parse_date(CURRENT_DATE)
    rf = np.where(receipt <= cur, rng.integers(0, 2, n_li), 2)  # 0=R 1=A 2=N
    ls = (ship > cur).astype(np.int64)  # 0=F 1=O
    out["lineitem"] = {
        "l_orderkey": l_ok,
        "l_partkey": l_pk,
        "l_suppkey": l_sk,
        "l_linenumber": l_ln,
        "l_quantity": qty * 100,
        "l_extendedprice": l_price,
        "l_discount": disc,
        "l_tax": tax,
        "l_returnflag": (["R", "A", "N"], rf),
        "l_linestatus": (["F", "O"], ls),
        "l_shipdate": ship,
        "l_commitdate": commit,
        "l_receiptdate": receipt,
        "l_shipinstruct": (SHIP_INSTRUCT,
                           rng.integers(0, len(SHIP_INSTRUCT), n_li)),
        "l_shipmode": (SHIP_MODES, rng.integers(0, len(SHIP_MODES), n_li)),
        "l_comment": _vocab_codes(_comment_vocab(rng, 1499, 44), rng, n_li),
    }

    # o_orderstatus: F if all lines F, O if all O, else P (spec 4.2.3)
    sums = np.zeros(n_ord + 1, dtype=np.int64)
    counts = np.zeros(n_ord + 1, dtype=np.int64)
    np.add.at(sums, l_ok, ls)
    np.add.at(counts, l_ok, 1)
    status = np.full(n_ord, 2, dtype=np.int64)  # 2=P
    status[sums[1:] == 0] = 0  # F
    status[sums[1:] == counts[1:]] = 1  # O
    out["orders"]["o_orderstatus"] = (["F", "O", "P"], status)
    # o_totalprice = sum(extendedprice*(1+tax)*(1-discount)) over lines,
    # computed in scaled-integer space then rounded back to cents
    line_total = l_price * (100 + tax) * (100 - disc) // 10000
    totals = np.zeros(n_ord + 1, dtype=np.int64)
    np.add.at(totals, l_ok, line_total)
    out["orders"]["o_totalprice"] = totals[1:]

    return out


def _line_numbers(lines_per: np.ndarray) -> np.ndarray:
    total = int(lines_per.sum())
    ln = np.arange(total, dtype=np.int64)
    starts = np.cumsum(lines_per) - lines_per
    return ln - np.repeat(starts, lines_per) + 1


def _dedup(strings: list[str]) -> tuple[list[str], np.ndarray]:
    """(vocab, codes) for a list that may contain duplicates."""
    vocab: list[str] = []
    index: dict[str, int] = {}
    codes = np.empty(len(strings), dtype=np.int64)
    for i, s in enumerate(strings):
        c = index.get(s)
        if c is None:
            c = len(vocab)
            vocab.append(s)
            index[s] = c
        codes[i] = c
    return vocab, codes


def _vocab_codes(vocab: list[str], rng: np.random.Generator,
                 n: int) -> tuple[list[str], np.ndarray]:
    return vocab, rng.integers(0, len(vocab), n, dtype=np.int64)

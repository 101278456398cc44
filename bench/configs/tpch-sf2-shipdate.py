"""TPC-H lineitem, clustered on l_shipdate, with Q1 and Q6.

Three parts, each used by the harness (``bench/harness.py``):

* ``generate(seed, cfg)``: the lineitem table by the value rules of the
  TPC-H specification v3, section 4.2.3, made with vectorised numpy.
* ``templates(api)``: Q1 and Q6 (sections 2.4.1 and 2.4.6) at their
  validation substitution parameters, staged on the engine's query API.
  ``api`` is handed in by the harness; this module imports nothing of the
  engine.
* ``reference(name, data, num)``: the same queries as plain numpy over
  the generated arrays, in float64. ``num`` rounds every input and every
  intermediate value; the harness passes the identity for the reference
  and a bfloat16 rounding for the control.
"""
from __future__ import annotations

import numpy as np

EPOCH = np.datetime64("1970-01-01", "D")


def day(iso: str) -> int:
    """Days since 1970-01-01, the engine's DATE representation."""
    return int((np.datetime64(iso, "D") - EPOCH).astype(np.int64))


START, END = day("1992-01-01"), day("1998-12-31")  # spec 4.2.3 STARTDATE/ENDDATE
CURRENT = day("1995-06-17")  # spec 4.2.3 CURRENTDATE
Q1_CUTOFF = day("1998-12-01") - 90  # Q1 validation DELTA = 90 (2.4.1.3)
Q6_FROM, Q6_TO = day("1994-01-01"), day("1995-01-01")  # Q6 DATE = 1994-01-01

SHIPINSTRUCT = np.array(["COLLECT COD", "DELIVER IN PERSON", "NONE",
                         "TAKE BACK RETURN"])  # spec 4.2.2.13, sorted
SHIPMODE = np.array(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP",
                     "TRUCK"])  # spec 4.2.2.13, sorted


def generate(seed: int, cfg: dict) -> dict:
    """Lineitem at ``cfg["scale_factor"]``, rows sorted by l_shipdate."""
    sf = float(cfg["scale_factor"])
    rng = np.random.default_rng(seed)
    n_orders = int(round(1_500_000 * sf))  # spec 4.2.5.1: SF * 1,500,000 orders
    n_parts = max(int(round(200_000 * sf)), 1)  # SF * 200,000 parts
    n_supp = max(int(round(10_000 * sf)), 4)  # SF * 10,000 suppliers
    # 4.2.3: O_ORDERDATE uniform in [STARTDATE, ENDDATE - 151 days]
    odate = rng.integers(START, END - 151 + 1, n_orders, dtype=np.int32)
    # 4.2.3: each order has a random number of lineitems in [1, 7]
    nlines = rng.integers(1, 8, n_orders)
    n = int(nlines.sum())
    # 4.2.3 note: O_ORDERKEY is sparse, only the first 8 of each 32 keys used
    idx = np.arange(n_orders, dtype=np.int64)
    okey = ((idx // 8) * 32 + idx % 8 + 1).astype(np.int32)
    first = np.repeat(np.cumsum(nlines) - nlines, nlines)
    linenumber = (np.arange(n) - first + 1).astype(np.int32)
    od = np.repeat(odate, nlines)
    # 4.2.3: L_PARTKEY random in [1, SF * 200,000]
    partkey = rng.integers(1, n_parts + 1, n, dtype=np.int64)
    # 4.2.3: L_SUPPKEY = (ps_partkey + i * (S/4 + (ps_partkey - 1)/S)) mod S + 1
    i = rng.integers(0, 4, n)
    suppkey = (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1
    # 4.2.3: L_QUANTITY random in [1, 50]
    quantity = rng.integers(1, 51, n).astype(np.int32)
    # 4.2.3: P_RETAILPRICE = (90000 + ((P_PARTKEY/10) mod 20001)
    #        + 100 * (P_PARTKEY mod 1000)) / 100
    retail_cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)
    # 4.2.3: L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE
    extendedprice = quantity * retail_cents / 100.0
    # 4.2.3: L_DISCOUNT random in [0.00, 0.10], L_TAX random in [0.00, 0.08]
    discount = rng.integers(0, 11, n) / 100.0
    tax = rng.integers(0, 9, n) / 100.0
    # 4.2.3: L_SHIPDATE = O_ORDERDATE + random [1, 121]
    shipdate = (od + rng.integers(1, 122, n)).astype(np.int32)
    # 4.2.3: L_COMMITDATE = O_ORDERDATE + random [30, 90]
    commitdate = (od + rng.integers(30, 91, n)).astype(np.int32)
    # 4.2.3: L_RECEIPTDATE = L_SHIPDATE + random [1, 30]
    receiptdate = (shipdate + rng.integers(1, 31, n)).astype(np.int32)
    # 4.2.3: L_RETURNFLAG = R or A at random if L_RECEIPTDATE <= CURRENTDATE,
    #        else N
    ra = np.where(rng.integers(0, 2, n) == 0, "R", "A")
    returnflag = np.where(receiptdate <= CURRENT, ra, "N")
    # 4.2.3: L_LINESTATUS = O if L_SHIPDATE > CURRENTDATE, else F
    linestatus = np.where(shipdate > CURRENT, "O", "F")
    # 4.2.3: L_SHIPINSTRUCT, L_SHIPMODE random from their lists; no query
    # reads them, nor L_COMMENT, so they are made as their dictionary codes
    # (the bytes the engine stores for them either way)
    shipinstruct = rng.integers(0, len(SHIPINSTRUCT), n).astype(np.int32)
    shipmode = rng.integers(0, len(SHIPMODE), n).astype(np.int32)
    # l_comment: near-unique text, made as near-unique dictionary codes
    comment = rng.integers(0, n, n).astype(np.int32)
    order = np.argsort(shipdate, kind="stable")  # clustered on l_shipdate
    fact = {
        "l_orderkey": okey[np.repeat(np.arange(n_orders), nlines)],
        "l_partkey": partkey.astype(np.int32),
        "l_suppkey": suppkey.astype(np.int32),
        "l_linenumber": linenumber,
        "l_quantity": quantity,
        "l_extendedprice": extendedprice,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": shipinstruct,
        "l_shipmode": shipmode,
        "l_comment": comment,
    }
    return {"fact": {k: v[order] for k, v in fact.items()}, "dims": {}}


# ---------------------------------------------------------------------------
# query templates, staged on the engine
# ---------------------------------------------------------------------------

# columns each template reads, for the roofline's byte count
COLUMNS = {
    "q1": ("l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
           "l_extendedprice", "l_discount", "l_tax"),
    "q6": ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice"),
}


def templates(api) -> dict:
    """name -> function staging the template on a fresh engine query.

    The map functions are made once here, so every staging of a template
    passes the same function objects (the serving layer's plan cache keys
    a map by its function's identity).
    """
    col, binary_op = api.col, api.binary_op

    def disc_price(env):
        return binary_op(env["l_extendedprice"], env["l_discount"],
                         lambda e, d: e * (1 - d))

    def charge(env):
        return binary_op(env["disc_price"], env["l_tax"],
                         lambda p, t: p * (1 + t))

    def revenue(env):
        return binary_op(env["l_extendedprice"], env["l_discount"],
                         lambda e, d: e * d)

    def q1(q, dims):
        return (q.filter(col("l_shipdate") <= Q1_CUTOFF)
                .map("disc_price", disc_price)
                .map("charge", charge)
                .groupby(["l_returnflag", "l_linestatus"],
                         {"sum_qty": ("sum", "l_quantity"),
                          "sum_base_price": ("sum", "l_extendedprice"),
                          "sum_disc_price": ("sum", "disc_price"),
                          "sum_charge": ("sum", "charge"),
                          "avg_qty": ("avg", "l_quantity"),
                          "avg_price": ("avg", "l_extendedprice"),
                          "avg_disc": ("avg", "l_discount"),
                          "count_order": ("count", None)},
                         num_groups_cap=8)
                .order_by(["l_returnflag", "l_linestatus"]))

    def q6(q, dims):
        return (q.filter((col("l_shipdate") >= Q6_FROM)
                         & (col("l_shipdate") < Q6_TO)
                         & col("l_discount").between(0.05, 0.07)
                         & (col("l_quantity") < 24))
                .map("revenue", revenue)
                .aggregate({"revenue": ("sum", "revenue")}))

    return {"q1": q1, "q6": q6}


# ---------------------------------------------------------------------------
# float64 numpy reference
# ---------------------------------------------------------------------------


def _group(keys, mask, values: dict):
    """SUM of each value column per distinct key tuple among masked rows,
    in ascending key order; returns (key tuples, {name: sums}, counts)."""
    cols = [np.asarray(k)[mask] for k in keys]
    codes = []
    uniq_per = []
    for c in cols:
        u, inv = np.unique(c, return_inverse=True)
        uniq_per.append(u)
        codes.append(inv)
    combo = np.zeros(len(cols[0]), np.int64)
    for u, inv in zip(uniq_per, codes):
        combo = combo * len(u) + inv
    uc, ginv = np.unique(combo, return_inverse=True)
    sums = {name: np.bincount(ginv, weights=np.asarray(v)[mask],
                              minlength=len(uc))
            for name, v in values.items()}
    counts = np.bincount(ginv, minlength=len(uc))
    tuples = []
    for g in uc:
        parts = []
        for u in reversed(uniq_per):
            parts.append(u[g % len(u)])
            g //= len(u)
        tuples.append(tuple(p.item() for p in reversed(parts)))
    return tuples, sums, counts


def reference(name: str, data: dict, num=lambda x: x) -> dict:
    """The answer to template ``name``: ``{"keys": [...], "rows": [(key
    tuple, {aggregate: value}), ...], "order": [(name, descending), ...]}``
    with the rows in the query's ORDER BY order."""
    f = data["fact"]
    ship = f["l_shipdate"]
    ext = num(np.asarray(f["l_extendedprice"], np.float64))
    disc = num(np.asarray(f["l_discount"], np.float64))
    qty = num(np.asarray(f["l_quantity"], np.float64))
    if name == "q1":
        tax = num(np.asarray(f["l_tax"], np.float64))
        mask = ship <= Q1_CUTOFF
        dp = num(ext * num(1 - disc))
        ch = num(dp * num(1 + tax))
        keys, s, cnt = _group([f["l_returnflag"], f["l_linestatus"]], mask,
                              {"qty": qty, "ext": ext, "dp": dp, "ch": ch,
                               "disc": disc})
        rows = []
        for i, k in enumerate(keys):
            c = float(cnt[i])
            rows.append((k, {"sum_qty": s["qty"][i],
                             "sum_base_price": s["ext"][i],
                             "sum_disc_price": s["dp"][i],
                             "sum_charge": s["ch"][i],
                             "avg_qty": s["qty"][i] / c,
                             "avg_price": s["ext"][i] / c,
                             "avg_disc": s["disc"][i] / c,
                             "count_order": c}))
        return {"keys": ["l_returnflag", "l_linestatus"], "rows": rows,
                "order": [("l_returnflag", False), ("l_linestatus", False)]}
    if name == "q6":
        d = np.asarray(f["l_discount"], np.float64)
        mask = ((ship >= Q6_FROM) & (ship < Q6_TO) & (d >= 0.05) & (d <= 0.07)
                & (np.asarray(f["l_quantity"]) < 24))
        rev = num(ext[mask] * disc[mask])
        return {"keys": [], "rows": [((), {"revenue": float(rev.sum())})]}
    raise KeyError(name)

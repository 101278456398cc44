"""Star Schema Benchmark: lineorder, its four dimensions and Q1.1-Q4.3.

Source: P. O'Neil, E. O'Neil, X. Chen, S. Revilak, "The Star Schema
Benchmark and Augmented Fact Table Indexing", TPCTC 2009: its schema, its
dbgen value rules (derived from TPC-H dbgen, spec v3 section 4.2.3) and its
13 queries with their fixed constants.

Three parts, each used by the harness (``bench/harness.py``):

* ``generate(seed, cfg)``: lineorder in dbgen's orderkey order (so
  ``lo_orderdate`` is unclustered), plus customer, supplier, part and date.
* ``templates(api)``: the 13 queries staged on the engine's query API,
  which the harness hands in; this module imports nothing of the engine.
* ``reference(name, data, num)``: the same queries as plain numpy joins
  over the generated arrays, in float64. ``num`` rounds every input and
  every intermediate value: the identity for the reference, a bfloat16
  rounding for the control.
"""
from __future__ import annotations

import math

import numpy as np

EPOCH = np.datetime64("1970-01-01", "D")
D_FIRST, D_LAST = np.datetime64("1992-01-01"), np.datetime64("1998-12-31")
# TPC-H 4.2.3: order dates end 151 days before ENDDATE
O_LAST = D_LAST - np.timedelta64(151, "D")

# TPC-H 4.2.3 nations, with the region each belongs to
NATIONS = [("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
           ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
           ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
           ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
           ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
           ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
           ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
MONTHS = ["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
          "Oct", "Nov", "Dec"]
N_PRIORITIES, N_SHIPMODES = 5, 7  # TPC-H 4.2.2.13 lists


def sizes(sf: float) -> dict:
    """Row counts at scale factor ``sf`` (SSB section 3)."""
    log_sf = 1 + math.floor(math.log2(sf)) if sf >= 1 else 1
    return {"orders": max(int(round(1_500_000 * sf)), 1),
            "customer": max(int(round(30_000 * sf)), 5),
            "supplier": max(int(round(2_000 * sf)), 5),
            "part": max(int(round(200_000 * log_sf * min(sf, 1.0))), 40)}


def _city(rng, n):
    """SSB city: the nation's name cut or padded to 9 letters plus a digit
    0-9; nation uniform over the 25."""
    nation = rng.integers(0, 25, n)
    names = np.array([nm for nm, _ in NATIONS])
    regions = np.array([REGIONS[r] for _, r in NATIONS])
    prefix = np.array([nm[:9].ljust(9) for nm, _ in NATIONS])
    digit = rng.integers(0, 10, n)
    city = np.char.add(prefix[nation], digit.astype("U1"))
    return city, names[nation], regions[nation]


def _yyyymmdd(days: np.ndarray) -> np.ndarray:
    d = days.astype("datetime64[D]")
    y = d.astype("datetime64[Y]").astype(np.int64) + 1970
    m = d.astype("datetime64[M]").astype(np.int64) % 12 + 1
    dd = (d - d.astype("datetime64[M]")).astype(np.int64) + 1
    return (y * 10000 + m * 100 + dd).astype(np.int32)


def generate(seed: int, cfg: dict) -> dict:
    sf = float(cfg["scale_factor"])
    n = sizes(sf)
    rng = np.random.default_rng(seed)
    # -- date: one row per day, 1992-01-01 .. 1998-12-31 (2,556 rows)
    days = np.arange(D_FIRST, D_LAST + np.timedelta64(1, "D"))
    year = days.astype("datetime64[Y]").astype(np.int64) + 1970
    month0 = days.astype("datetime64[M]").astype(np.int64) % 12
    doy = (days - days.astype("datetime64[Y]")).astype(np.int64)
    date = {"d_datekey": _yyyymmdd(days.astype(np.int64)),
            "d_year": year.astype(np.int32),
            "d_yearmonthnum": (year * 100 + month0 + 1).astype(np.int32),
            "d_yearmonth": np.char.add(np.array(MONTHS)[month0],
                                       year.astype("U4")),
            "d_weeknuminyear": (doy // 7 + 1).astype(np.int32)}
    dow = (days.astype(np.int64) + 3) % 7  # 1970-01-01 was a Thursday
    dom = (days - days.astype("datetime64[M]")).astype(np.int64)
    last_of_month = (days + np.timedelta64(1, "D")).astype("datetime64[M]") \
        != days.astype("datetime64[M]")
    n_days = len(days)
    date.update({  # columns no query reads, as dictionary codes or numbers
        "d_date": np.arange(n_days, dtype=np.int32),
        "d_dayofweek": dow.astype(np.int32),
        "d_month": month0.astype(np.int32),
        "d_daynuminweek": (dow + 1).astype(np.int32),
        "d_daynuminmonth": (dom + 1).astype(np.int32),
        "d_daynuminyear": (doy + 1).astype(np.int32),
        "d_monthnuminyear": (month0 + 1).astype(np.int32),
        "d_sellingseason": (month0 // 3 % 5).astype(np.int32),
        "d_lastdayinweekfl": (dow == 6).astype(np.int32),
        "d_lastdayinmonthfl": last_of_month.astype(np.int32),
        "d_holidayfl": rng.integers(0, 2, n_days).astype(np.int32),
        "d_weekdayfl": (dow < 5).astype(np.int32)})
    # -- customer / supplier: city, nation, region; segment for customer
    c_city, c_nation, c_region = _city(rng, n["customer"])
    nc, ns = n["customer"], n["supplier"]
    # names are unique, addresses and phones near-unique: dictionary codes
    customer = {"c_custkey": np.arange(1, nc + 1, dtype=np.int32),
                "c_name": np.arange(nc, dtype=np.int32),
                "c_address": rng.permutation(nc).astype(np.int32),
                "c_city": c_city, "c_nation": c_nation, "c_region": c_region,
                "c_phone": rng.permutation(nc).astype(np.int32),
                "c_mktsegment": np.array(SEGMENTS)[rng.integers(0, 5, nc)]}
    s_city, s_nation, s_region = _city(rng, n["supplier"])
    supplier = {"s_suppkey": np.arange(1, ns + 1, dtype=np.int32),
                "s_name": np.arange(ns, dtype=np.int32),
                "s_address": rng.permutation(ns).astype(np.int32),
                "s_city": s_city, "s_nation": s_nation, "s_region": s_region,
                "s_phone": rng.permutation(ns).astype(np.int32)}
    # -- part: mfgr 1-5, category mfgr + 1-5, brand1 category + 1-40
    mfgr = rng.integers(1, 6, n["part"])
    cat = rng.integers(1, 6, n["part"])
    brand = rng.integers(1, 41, n["part"])
    p_mfgr = np.char.add("MFGR#", mfgr.astype("U1"))
    p_category = np.char.add(p_mfgr, cat.astype("U1"))
    partkey = np.arange(1, n["part"] + 1, dtype=np.int64)
    npart = n["part"]
    part = {"p_partkey": partkey.astype(np.int32),
            "p_name": rng.integers(0, npart, npart).astype(np.int32),
            "p_mfgr": p_mfgr, "p_category": p_category,
            "p_brand1": np.char.add(p_category, brand.astype("U2")),
            # TPC-H 4.2.2.13 lists: 92 colors, 150 types, 40 containers
            "p_color": rng.integers(0, 92, npart).astype(np.int32),
            "p_type": rng.integers(0, 150, npart).astype(np.int32),
            "p_size": rng.integers(1, 51, npart).astype(np.int32),
            "p_container": rng.integers(0, 40, npart).astype(np.int32)}
    # -- lineorder: TPC-H 4.2.3 order and lineitem rules
    n_orders = n["orders"]
    nlines = rng.integers(1, 8, n_orders)  # 1-7 lines per order
    rows = int(nlines.sum())
    idx = np.arange(n_orders, dtype=np.int64)
    okey = ((idx // 8) * 32 + idx % 8 + 1).astype(np.int32)  # sparse keys
    first = np.repeat(np.cumsum(nlines) - nlines, nlines)
    # custkey uniform over customers, skipping multiples of 3 (TPC-H 4.2.3)
    c_ok = np.arange(1, n["customer"] + 1)
    c_ok = c_ok[c_ok % 3 != 0] if n["customer"] >= 3 else c_ok
    o_cust = c_ok[rng.integers(0, len(c_ok), n_orders)].astype(np.int32)
    o_date = rng.integers(0, int((O_LAST - D_FIRST).astype(np.int64)) + 1,
                          n_orders) + (D_FIRST - EPOCH).astype(np.int64)
    o_prio = rng.integers(0, N_PRIORITIES, n_orders).astype(np.int32)
    od = np.repeat(o_date, nlines)
    partkey_l = rng.integers(1, n["part"] + 1, rows)
    quantity = rng.integers(1, 51, rows).astype(np.int32)
    # retail price in cents (TPC-H 4.2.3 P_RETAILPRICE)
    retail = 90_000 + (partkey_l // 10) % 20_001 + 100 * (partkey_l % 1000)
    extendedprice = (quantity * retail).astype(np.int32)
    discount = rng.integers(0, 11, rows).astype(np.int32)  # percent
    tax = rng.integers(0, 9, rows).astype(np.int32)  # percent
    # SSB: revenue = extendedprice * (100 - discount) / 100,
    #      supplycost = 6 * retailprice / 10
    revenue = (extendedprice.astype(np.int64) * (100 - discount) // 100)
    supplycost = (6 * retail // 10).astype(np.int32)
    # ordtotalprice: TPC-H O_TOTALPRICE = sum of extprice*(1+tax)*(1-disc)
    line_total = extendedprice.astype(np.int64) * (100 + tax) * (100 - discount)
    order_total = np.add.reduceat(line_total, np.cumsum(nlines) - nlines)
    lineorder = {
        "lo_orderkey": np.repeat(okey, nlines),
        "lo_linenumber": (np.arange(rows) - first + 1).astype(np.int32),
        "lo_custkey": np.repeat(o_cust, nlines),
        "lo_partkey": partkey_l.astype(np.int32),
        "lo_suppkey": rng.integers(1, n["supplier"] + 1, rows).astype(np.int32),
        "lo_orderdate": _yyyymmdd(od),
        # orderpriority, shippriority and shipmode are read by no query:
        # made as their dictionary codes, the bytes the engine stores
        "lo_orderpriority": np.repeat(o_prio, nlines),
        "lo_shippriority": np.zeros(rows, np.int32),
        "lo_quantity": quantity,
        "lo_extendedprice": extendedprice,
        "lo_ordtotalprice": np.repeat(order_total // 10_000, nlines).astype(
            np.int32),
        "lo_discount": discount,
        "lo_revenue": revenue.astype(np.int32),
        "lo_supplycost": supplycost,
        "lo_tax": tax,
        "lo_commitdate": _yyyymmdd(od + rng.integers(30, 91, rows)),
        "lo_shipmode": rng.integers(0, N_SHIPMODES, rows).astype(np.int32),
    }
    return {"fact": lineorder,
            "dims": {"date": date, "customer": customer,
                     "supplier": supplier, "part": part}}


# ---------------------------------------------------------------------------
# query templates, staged on the engine
# ---------------------------------------------------------------------------

# dimension -> (its key, the lineorder foreign key)
JOINS = {"date": ("d_datekey", "lo_orderdate"),
         "customer": ("c_custkey", "lo_custkey"),
         "supplier": ("s_suppkey", "lo_suppkey"),
         "part": ("p_partkey", "lo_partkey")}

# fact columns each template reads, for the roofline's byte count
COLUMNS = {
    "q1.1": ("lo_orderdate", "lo_discount", "lo_quantity", "lo_extendedprice"),
    "q1.2": ("lo_orderdate", "lo_discount", "lo_quantity", "lo_extendedprice"),
    "q1.3": ("lo_orderdate", "lo_discount", "lo_quantity", "lo_extendedprice"),
    "q2.1": ("lo_orderdate", "lo_partkey", "lo_suppkey", "lo_revenue"),
    "q2.2": ("lo_orderdate", "lo_partkey", "lo_suppkey", "lo_revenue"),
    "q2.3": ("lo_orderdate", "lo_partkey", "lo_suppkey", "lo_revenue"),
    "q3.1": ("lo_custkey", "lo_suppkey", "lo_orderdate", "lo_revenue"),
    "q3.2": ("lo_custkey", "lo_suppkey", "lo_orderdate", "lo_revenue"),
    "q3.3": ("lo_custkey", "lo_suppkey", "lo_orderdate", "lo_revenue"),
    "q3.4": ("lo_custkey", "lo_suppkey", "lo_orderdate", "lo_revenue"),
    "q4.1": ("lo_custkey", "lo_suppkey", "lo_partkey", "lo_orderdate",
             "lo_revenue", "lo_supplycost"),
    "q4.2": ("lo_custkey", "lo_suppkey", "lo_partkey", "lo_orderdate",
             "lo_revenue", "lo_supplycost"),
    "q4.3": ("lo_custkey", "lo_suppkey", "lo_partkey", "lo_orderdate",
             "lo_revenue", "lo_supplycost"),
}

# Each query as data: dimension filters (column, op, literal), the
# dimension attributes it groups by, fact filters, the measure, the
# grouping and the ORDER BY (a leading "-" sorts descending).
UK = ("UNITED KI1", "UNITED KI5")
QUERIES = {
    "q1.1": {"dims": {"date": [("d_year", "eq", 1993)]},
             "fact": [("lo_discount", "between", (1, 3)),
                      ("lo_quantity", "lt", 25)],
             "measure": "ext_disc", "group": [], "order": []},
    "q1.2": {"dims": {"date": [("d_yearmonthnum", "eq", 199401)]},
             "fact": [("lo_discount", "between", (4, 6)),
                      ("lo_quantity", "between", (26, 35))],
             "measure": "ext_disc", "group": [], "order": []},
    "q1.3": {"dims": {"date": [("d_weeknuminyear", "eq", 6),
                               ("d_year", "eq", 1994)]},
             "fact": [("lo_discount", "between", (5, 7)),
                      ("lo_quantity", "between", (26, 35))],
             "measure": "ext_disc", "group": [], "order": []},
    "q2.1": {"dims": {"part": [("p_category", "eq", "MFGR#12")],
                      "supplier": [("s_region", "eq", "AMERICA")],
                      "date": []},
             "measure": "lo_revenue", "group": ["d_year", "p_brand1"],
             "order": ["d_year", "p_brand1"]},
    "q2.2": {"dims": {"part": [("p_brand1", "between",
                                ("MFGR#2221", "MFGR#2228"))],
                      "supplier": [("s_region", "eq", "ASIA")],
                      "date": []},
             "measure": "lo_revenue", "group": ["d_year", "p_brand1"],
             "order": ["d_year", "p_brand1"]},
    "q2.3": {"dims": {"part": [("p_brand1", "eq", "MFGR#2239")],
                      "supplier": [("s_region", "eq", "EUROPE")],
                      "date": []},
             "measure": "lo_revenue", "group": ["d_year", "p_brand1"],
             "order": ["d_year", "p_brand1"]},
    "q3.1": {"dims": {"customer": [("c_region", "eq", "ASIA")],
                      "supplier": [("s_region", "eq", "ASIA")],
                      "date": [("d_year", "between", (1992, 1997))]},
             "measure": "lo_revenue",
             "group": ["c_nation", "s_nation", "d_year"],
             "order": ["d_year", "-revenue"]},
    "q3.2": {"dims": {"customer": [("c_nation", "eq", "UNITED STATES")],
                      "supplier": [("s_nation", "eq", "UNITED STATES")],
                      "date": [("d_year", "between", (1992, 1997))]},
             "measure": "lo_revenue", "group": ["c_city", "s_city", "d_year"],
             "order": ["d_year", "-revenue"]},
    "q3.3": {"dims": {"customer": [("c_city", "isin", UK)],
                      "supplier": [("s_city", "isin", UK)],
                      "date": [("d_year", "between", (1992, 1997))]},
             "measure": "lo_revenue", "group": ["c_city", "s_city", "d_year"],
             "order": ["d_year", "-revenue"]},
    "q3.4": {"dims": {"customer": [("c_city", "isin", UK)],
                      "supplier": [("s_city", "isin", UK)],
                      "date": [("d_yearmonth", "eq", "Dec1997")]},
             "measure": "lo_revenue", "group": ["c_city", "s_city", "d_year"],
             "order": ["d_year", "-revenue"]},
    "q4.1": {"dims": {"customer": [("c_region", "eq", "AMERICA")],
                      "supplier": [("s_region", "eq", "AMERICA")],
                      "part": [("p_mfgr", "isin", ("MFGR#1", "MFGR#2"))],
                      "date": []},
             "measure": "profit", "group": ["d_year", "c_nation"],
             "order": ["d_year", "c_nation"]},
    "q4.2": {"dims": {"customer": [("c_region", "eq", "AMERICA")],
                      "supplier": [("s_region", "eq", "AMERICA")],
                      "part": [("p_mfgr", "isin", ("MFGR#1", "MFGR#2"))],
                      "date": [("d_year", "isin", (1997, 1998))]},
             "measure": "profit",
             "group": ["d_year", "s_nation", "p_category"],
             "order": ["d_year", "s_nation", "p_category"]},
    "q4.3": {"dims": {"customer": [("c_region", "eq", "AMERICA")],
                      "supplier": [("s_nation", "eq", "UNITED STATES")],
                      "part": [("p_category", "eq", "MFGR#14")],
                      "date": [("d_year", "isin", (1997, 1998))]},
             "measure": "profit", "group": ["d_year", "s_city", "p_brand1"],
             "order": ["d_year", "s_city", "p_brand1"]},
}
# the name each query gives its measure's sum
AGG_NAME = {"ext_disc": "revenue", "lo_revenue": "revenue",
            "profit": "profit"}


def templates(api) -> dict:
    """name -> function(q, dims) staging the query on a fresh engine
    query; ``dims`` maps dimension names to the engine's resident tables.
    The map functions are made once here, so every staging passes the same
    function objects (the plan cache keys a map by identity)."""
    col, binary_op = api.col, api.binary_op

    def ext_disc(env):
        return binary_op(env["lo_extendedprice"], env["lo_discount"],
                         lambda e, d: e * d)

    def profit(env):
        return binary_op(env["lo_revenue"], env["lo_supplycost"],
                         lambda r, c: r - c)

    maps = {"ext_disc": ext_disc, "profit": profit}

    def pred(column, op, lit):
        c = col(column)
        if op == "eq":
            return c == lit
        if op == "lt":
            return c < lit
        if op == "between":
            return c.between(*lit)
        if op == "isin":
            return c.isin(lit)
        raise ValueError(op)

    def conj(preds):
        out = None
        for p in preds:
            out = p if out is None else out & p
        return out

    def make(spec):
        def stage(q, dims):
            for dim, filters in spec["dims"].items():
                key, fk = JOINS[dim]
                wanted = [g for g in spec["group"] if g in dims[dim].columns]
                where = conj([pred(*f) for f in filters]) if filters else None
                q = q.join(dims[dim], fk=fk, on=key, cols=wanted, where=where)
            if spec.get("fact"):
                q = q.filter(conj([pred(*f) for f in spec["fact"]]))
            measure = spec["measure"]
            if measure in maps:
                q = q.map(measure, maps[measure])
            agg = {AGG_NAME[measure]: ("sum", measure)}
            if not spec["group"]:
                return q.aggregate(agg)
            q = q.groupby(spec["group"], agg, num_groups_cap=1024)
            by = [o.lstrip("-") for o in spec["order"]]
            return q.order_by(by, descending=[o.startswith("-")
                                               for o in spec["order"]])
        return stage

    return {name: make(spec) for name, spec in QUERIES.items()}


# ---------------------------------------------------------------------------
# float64 numpy reference
# ---------------------------------------------------------------------------


def _dim_mask(dim: dict, filters) -> np.ndarray:
    n = len(next(iter(dim.values())))
    mask = np.ones(n, bool)
    for column, op, lit in filters:
        v = dim[column]
        if op == "eq":
            mask &= v == lit
        elif op == "lt":
            mask &= v < lit
        elif op == "between":
            mask &= (v >= lit[0]) & (v <= lit[1])
        elif op == "isin":
            mask &= np.isin(v, list(lit))
        else:
            raise ValueError(op)
    return mask


def _lookup(keys: np.ndarray):
    """A dense table from key value to dimension row (-1: no such key)."""
    lo = int(keys.min())
    lut = np.full(int(keys.max()) - lo + 2, -1, np.int64)
    lut[keys - lo] = np.arange(len(keys))
    return lut, lo


def reference(name: str, data: dict, num=lambda x: x) -> dict:
    """The answer to query ``name``: ``{"keys": [...], "rows": [(key
    tuple, {aggregate: value}), ...], "order": [(name, descending), ...]}``
    with the rows in the query's ORDER BY order."""
    spec = QUERIES[name]
    f, dims = data["fact"], data["dims"]
    mask = np.ones(len(f["lo_orderkey"]), bool)
    for column, op, lit in spec.get("fact", []):
        v = f[column]
        if op == "lt":
            mask &= v < lit
        elif op == "between":
            mask &= (v >= lit[0]) & (v <= lit[1])
        else:
            raise ValueError(op)
    idx = np.flatnonzero(mask)  # fact rows still joined
    pos = {}  # dimension -> its row for each fact row in idx
    for dim, filters in spec["dims"].items():
        key, fk = JOINS[dim]
        d = dims[dim]
        lut, lo = _lookup(d[key])
        fkv = f[fk][idx] - lo
        inside = (fkv >= 0) & (fkv < len(lut) - 1)
        row = np.where(inside, lut[np.clip(fkv, 0, len(lut) - 1)], -1)
        keep = np.append(_dim_mask(d, filters), False)  # row -1: no match
        sel = keep[row]
        idx = idx[sel]
        pos = {k: v[sel] for k, v in pos.items()}
        pos[dim] = row[sel]
    measure = spec["measure"]
    if measure == "ext_disc":
        vals = num(num(f["lo_extendedprice"][idx].astype(np.float64))
                   * num(f["lo_discount"][idx].astype(np.float64)))
    elif measure == "profit":
        vals = num(num(f["lo_revenue"][idx].astype(np.float64))
                   - num(f["lo_supplycost"][idx].astype(np.float64)))
    else:
        vals = num(f[measure][idx].astype(np.float64))
    agg = AGG_NAME[measure]
    order = [(o.lstrip("-"), o.startswith("-")) for o in spec["order"]]
    if not spec["group"]:
        return {"keys": [], "rows": [((), {agg: float(vals.sum())})],
                "order": order}
    keys = list(spec["group"])
    if not len(vals):
        return {"keys": keys, "rows": [], "order": order}
    cols = []
    for g in keys:
        dim = next(dn for dn in spec["dims"] if g in dims[dn])
        cols.append(dims[dim][g][pos[dim]])
    uniq, inv = np.unique(np.stack([np.unique(c, return_inverse=True)[1]
                                    for c in cols], axis=1),
                          axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    sums = np.bincount(inv, weights=vals, minlength=len(uniq))
    first = np.zeros(len(uniq), np.int64)
    first[inv[::-1]] = np.arange(len(inv))[::-1]
    rows = [(tuple(c[first[i]].item() for c in cols), {agg: float(sums[i])})
            for i in range(len(uniq))]

    def sort_key(row):
        return tuple(_Desc(row[1][n] if n == agg else row[0][keys.index(n)])
                     if desc else
                     (row[1][n] if n == agg else row[0][keys.index(n)])
                     for n, desc in order)

    rows.sort(key=sort_key)
    return {"keys": keys, "rows": rows, "order": order}


class _Desc:
    """Reverses the order of a value inside a sort key."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __lt__(self, other):
        return self.v > other.v

    def __eq__(self, other):
        return self.v == other.v

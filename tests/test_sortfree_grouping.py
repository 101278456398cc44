"""Sort-free (bounded-domain scatter) grouping vs the argsort path.

Deterministic seeded tests (no hypothesis dependency — this file IS the
tier-1 conformance floor for the DESIGN.md §5 grouping paths): the
sort-free path must produce GroupByResults IDENTICAL to the argsort path
for every encoding mix, including the hybrid run-level path, and the plan
layer must engage/disable it exactly per the domain-metadata contract.

Also hosts the deterministic primitive regressions that back the
hypothesis variants in test_primitives.py (which skip when hypothesis is
absent): the range_union int32-overflow fix and the k-way fused
intersect's run-boundary preservation.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import compress
from repro.core import encodings as E
from repro.core import groupby as G
from repro.core import primitives as P
from repro.core.partition import PartitionedQuery, PartitionedTable
from repro.core.plan import Query, col
from repro.core.table import Table
from repro.kernels import dispatch

from conftest import MASK_ENCODERS, make_rle_col

CFG = compress.CompressionConfig(plain_threshold=1000)


# ---------------------------------------------------------------------------
# primitive regressions (deterministic mirrors of test_primitives.py)
# ---------------------------------------------------------------------------


def test_range_union_no_int32_overflow_near_2_31_rows():
    """The old ``pos * 2 + (delta < 0)`` sort key wrapped int32 past 2^30
    rows; positions near the top of the int32 row space must still union."""
    nrows = 2**31 - 8
    m1 = E.make_rle_mask([nrows - 1000], [nrows - 500], nrows, capacity=3)
    m2 = E.make_rle_mask([nrows - 700], [nrows - 100], nrows, capacity=3)
    s, e, cnt = P.range_union(m1.starts, m1.ends, m1.n, m2.starts, m2.ends,
                              m2.n, nrows, cap_out=6)
    assert int(cnt) == 1
    assert int(np.asarray(s)[0]) == nrows - 1000
    assert int(np.asarray(e)[0]) == nrows - 100
    # adjacent runs at huge positions still merge maximally
    m3 = E.make_rle_mask([nrows - 400], [nrows - 301], nrows, capacity=3)
    m4 = E.make_rle_mask([nrows - 300], [nrows - 200], nrows, capacity=3)
    s, e, cnt = P.range_union(m3.starts, m3.ends, m3.n, m4.starts, m4.ends,
                              m4.n, nrows, cap_out=6)
    assert int(cnt) == 1
    assert int(np.asarray(s)[0]) == nrows - 400
    assert int(np.asarray(e)[0]) == nrows - 200


def test_unique_bounded_matches_unique_with_inverse(rng):
    for _ in range(30):
        n = int(rng.integers(1, 60))
        a = rng.integers(0, 10, n).astype(np.int32)
        valid = rng.random(n) > 0.3
        if not valid.any():
            continue
        jv = jnp.asarray(valid)
        u1, i1, n1 = P.unique_with_inverse(jnp.asarray(a), jv, cap_groups=16)
        u2, i2, n2 = P.unique_bounded(jnp.asarray(a), jv, domain_size=10,
                                      cap_groups=16)
        k = int(n1)
        assert k == int(n2) == len(np.unique(a[valid]))
        np.testing.assert_array_equal(np.asarray(u1)[:k], np.asarray(u2)[:k])
        np.testing.assert_array_equal(np.asarray(i1)[valid],
                                      np.asarray(i2)[valid])


def test_range_intersect_multi_preserves_run_boundaries(rng):
    """Alignment contract: k-way fused sweep segments never span a source
    run boundary (adjacent runs with different values stay split)."""
    for _ in range(30):
        k = int(rng.integers(1, 4))
        n = int(rng.integers(4, 40))
        cols = [rng.integers(0, 3, n).astype(np.int32) for _ in range(k)]
        rles = [make_rle_col(v) for v in cols]
        cap = sum(c.capacity for c in rles)
        s, e, idxs, cnt = P.range_intersect_multi(
            [(c.starts, c.ends, c.n) for c in rles], n, cap)
        change = np.zeros(n, bool)
        change[0] = True
        for v in cols:
            change[1:] |= v[1:] != v[:-1]
        ws = np.flatnonzero(change)
        we = np.concatenate([ws[1:] - 1, [n - 1]])
        kc = int(cnt)
        assert kc == len(ws)
        np.testing.assert_array_equal(np.asarray(s)[:kc], ws)
        np.testing.assert_array_equal(np.asarray(e)[:kc], we)
        for j, v in enumerate(cols):
            np.testing.assert_array_equal(
                np.asarray(rles[j].values)[np.asarray(idxs[j])[:kc]], v[ws])


def test_range_intersect_multi_gapped_coverage(rng):
    for _ in range(30):
        k = int(rng.integers(1, 5))
        n = int(rng.integers(4, 50))
        denses = [rng.random(n) > 0.4 for _ in range(k)]
        masks = [MASK_ENCODERS["rle"](d) for d in denses]
        cap = sum(m.capacity for m in masks)
        s, e, idxs, cnt = P.range_intersect_multi(
            [(m.starts, m.ends, m.n) for m in masks], n, cap)
        got = np.asarray(E.decode_rle_coverage(s, e, cnt, n))
        np.testing.assert_array_equal(got, np.logical_and.reduce(denses))


# ---------------------------------------------------------------------------
# groupby_aggregate: sort-free vs argsort identity, all encoding mixes
# ---------------------------------------------------------------------------

SPECS = [("s", "sum", "v"), ("c", "count", None), ("mn", "min", "v"),
         ("mx", "max", "v"), ("av", "avg", "v")]


def _encode_key(kind, vals):
    if kind == "plain":
        return E.make_plain(vals)
    if kind == "rle":
        return make_rle_col(vals)
    if kind == "index":
        return E.make_index(vals, np.arange(len(vals)), nrows=len(vals),
                            capacity=len(vals) + 4)
    raise ValueError(kind)


def _assert_identical(r1: G.GroupByResult, r2: G.GroupByResult):
    assert int(r1.num_groups) == int(r2.num_groups)
    np.testing.assert_array_equal(np.asarray(r1.valid), np.asarray(r2.valid))
    for k in r1.keys:
        np.testing.assert_array_equal(np.asarray(r1.keys[k]),
                                      np.asarray(r2.keys[k]))
    for a in r1.aggs:
        np.testing.assert_array_equal(np.asarray(r1.aggs[a]),
                                      np.asarray(r2.aggs[a]))


@pytest.mark.parametrize("kenc", ["plain", "rle", "index"])
@pytest.mark.parametrize("venc", ["plain", "rle"])
@pytest.mark.parametrize("menc", [None, "plain", "rle", "index"])
def test_sortfree_identical_to_argsort(rng, kenc, venc, menc):
    n = 400
    keys = np.sort(rng.integers(-3, 4, n)).astype(np.int32)  # negative lo
    vals = rng.integers(0, 50, n).astype(np.float32)
    sel = rng.random(n) > 0.25
    cols = {"k": _encode_key(kenc, keys),
            "v": E.make_plain(vals) if venc == "plain" else make_rle_col(vals)}
    mask = MASK_ENCODERS[menc](sel) if menc else None
    domains = {"k": compress.column_domain(keys)}
    r_fast = G.groupby_aggregate(cols, ["k"], SPECS, num_groups_cap=16,
                                 mask=mask, key_domains=domains)
    r_sort = G.groupby_aggregate(cols, ["k"], SPECS, num_groups_cap=16,
                                 mask=mask, key_domains=None)
    _assert_identical(r_fast, r_sort)


def test_sortfree_multi_key_mixed_radix(rng):
    """Two-column key composed by mixed-radix over EXACT domain sizes."""
    n = 500
    k1 = np.sort(rng.integers(0, 3, n)).astype(np.int32)
    k2 = rng.integers(-2, 3, n).astype(np.int32)
    vals = rng.random(n).astype(np.float32)
    cols = {"a": make_rle_col(k1), "b": E.make_plain(k2),
            "v": E.make_plain(vals)}
    domains = {"a": compress.column_domain(k1),
               "b": compress.column_domain(k2)}
    r_fast = G.groupby_aggregate(cols, ["a", "b"], SPECS, num_groups_cap=32,
                                 key_domains=domains)
    r_sort = G.groupby_aggregate(cols, ["a", "b"], SPECS, num_groups_cap=32)
    _assert_identical(r_fast, r_sort)
    # oracle spot check
    ng = int(r_fast.num_groups)
    pairs = set(zip(k1.tolist(), k2.tolist()))
    assert ng == len(pairs)


def test_sortfree_hybrid_run_level_path(rng):
    """Hybrid path (§7/A.2): position-explicit keys, Plain aggregates —
    grouping runs at run level; sort-free must slot in identically."""
    n = 600
    keys = np.sort(rng.integers(0, 5, n)).astype(np.int32)
    vals = rng.random(n).astype(np.float32)
    sel = rng.random(n) > 0.3
    cols = {"k": make_rle_col(keys), "v": E.make_plain(vals)}
    specs = SPECS + [("sd", "std", "v")]
    for menc in (None, "rle", "index"):
        mask = MASK_ENCODERS[menc](sel) if menc else None
        r_fast = G.groupby_aggregate(
            cols, ["k"], specs, num_groups_cap=8, mask=mask,
            key_domains={"k": compress.column_domain(keys)})
        r_sort = G.groupby_aggregate(cols, ["k"], specs, num_groups_cap=8,
                                     mask=mask)
        _assert_identical(r_fast, r_sort)


def test_sortfree_falls_back_without_metadata_or_oversized_domain(rng, monkeypatch):
    n = 200
    keys = rng.integers(0, 4, n).astype(np.int32)
    vals = rng.random(n).astype(np.float32)
    cols = {"k": E.make_plain(keys), "v": E.make_plain(vals)}
    calls = {"bounded": 0, "argsort": 0}
    real_b, real_u = P.unique_bounded, P.unique_with_inverse

    def count_b(*a, **kw):
        calls["bounded"] += 1
        return real_b(*a, **kw)

    def count_u(*a, **kw):
        calls["argsort"] += 1
        return real_u(*a, **kw)

    monkeypatch.setattr(P, "unique_bounded", count_b)
    monkeypatch.setattr(P, "unique_with_inverse", count_u)
    dom = {"k": compress.column_domain(keys)}
    G.groupby_aggregate(cols, ["k"], SPECS, 16, key_domains=dom)
    assert calls == {"bounded": 1, "argsort": 0}
    # domain over the policy cap -> argsort
    calls.update(bounded=0, argsort=0)
    with dispatch.overrides(sort_free_max_domain=2):
        G.groupby_aggregate(cols, ["k"], SPECS, 16, key_domains=dom)
    assert calls["bounded"] == 0 and calls["argsort"] > 0
    # policy kill switch
    calls.update(bounded=0, argsort=0)
    with dispatch.overrides(enable_sort_free=False):
        G.groupby_aggregate(cols, ["k"], SPECS, 16, key_domains=dom)
    assert calls["bounded"] == 0 and calls["argsort"] > 0
    # a domain whose bounds exceed int32 (uint32-style keys past 2^31)
    # must fall back to argsort, not crash the int32 code arithmetic
    calls.update(bounded=0, argsort=0)
    wide = {"k": (2**31 + 5, 5)}
    G.groupby_aggregate(cols, ["k"], SPECS, 16, key_domains=wide)
    assert calls["bounded"] == 0 and calls["argsort"] > 0
    # float keys never take the scatter path even with (bogus) metadata
    calls.update(bounded=0, argsort=0)
    fcols = {"k": E.make_plain(keys.astype(np.float32)),
             "v": E.make_plain(vals)}
    G.groupby_aggregate(fcols, ["k"], SPECS, 16, key_domains=dom)
    assert calls["bounded"] == 0 and calls["argsort"] > 0


# ---------------------------------------------------------------------------
# plan layer: domain threading, map invalidation, dictionary keys
# ---------------------------------------------------------------------------


def test_query_dictionary_keys_take_sortfree_path(rng, monkeypatch):
    n = 30_000
    data = {"k": np.array(["ant", "bee", "cow", "doe"])[rng.integers(0, 4, n)],
            "v": rng.random(n).astype(np.float32)}
    t = Table.from_arrays(data, cfg=CFG)
    assert t.domains["k"] == (0, 4)
    calls = []
    real = P.unique_with_inverse
    monkeypatch.setattr(P, "unique_with_inverse",
                        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])
    q = (Query(t).filter(col("v") > 0.5)
         .groupby(["k"], {"s": ("sum", "v"), "c": ("count", None)},
                  num_groups_cap=8))
    r = q.run()
    assert not calls  # the argsort unique never ran
    ng = int(r.num_groups)
    assert ng == 4
    sel = data["v"] > 0.5
    for i in range(ng):
        key = t.dictionaries["k"][int(np.asarray(r.keys["k"])[i])]
        m = sel & (data["k"] == key)
        assert int(np.asarray(r.aggs["c"])[i]) == int(m.sum())
        np.testing.assert_allclose(float(np.asarray(r.aggs["s"])[i]),
                                   data["v"][m].sum(), rtol=1e-4)


def test_map_rebinding_disables_stale_key_domain(rng):
    """A group column rewritten by map() must NOT group under the stale
    ingest domain (out-of-range codes would be silently dropped)."""
    from repro.core import arithmetic
    n = 2000
    data = {"g": rng.integers(0, 4, n).astype(np.int32),
            "v": rng.random(n).astype(np.float32)}
    t = Table.from_arrays(data, cfg=CFG)
    q = (Query(t)
         .map("g", lambda env: arithmetic.scalar_op(env["g"], "add", 100))
         .groupby(["g"], {"c": ("count", None)}, num_groups_cap=8))
    r = q.run()
    ng = int(r.num_groups)
    assert ng == 4
    got_keys = np.sort(np.asarray(r.keys["g"])[:ng])
    np.testing.assert_array_equal(got_keys, np.arange(100, 104))
    assert int(np.asarray(r.aggs["c"])[:ng].sum()) == n


def test_partitioned_sortfree_matches_argsort(rng):
    n = 20_000
    data = {"k": np.array(["x", "y", "z"])[rng.integers(0, 3, n)],
            "g": rng.integers(10, 15, n).astype(np.int32),
            "v": rng.random(n).astype(np.float32)}
    pt = PartitionedTable.from_arrays(data, cfg=CFG, num_partitions=5)
    assert pt.domains["k"] == (0, 3)
    assert pt.domains["g"] == (10, 15 - 10)

    def run_query():
        return (PartitionedQuery(PartitionedTable.from_arrays(
                    data, cfg=CFG, num_partitions=5))
                .filter(col("v") > 0.4)
                .groupby(["k", "g"], {"s": ("sum", "v"), "c": ("count", None),
                                      "a": ("avg", "v")},
                         num_groups_cap=32).run())

    r_fast = run_query()
    with dispatch.overrides(enable_sort_free=False):
        r_sort = run_query()
    assert r_fast.num_groups == r_sort.num_groups
    for k in r_fast.keys:
        np.testing.assert_array_equal(r_fast.keys[k], r_sort.keys[k])
    for a in r_fast.aggs:
        np.testing.assert_allclose(r_fast.aggs[a], r_sort.aggs[a], rtol=1e-6)


# ---------------------------------------------------------------------------
# dense small-table reductions (dispatch.G_DENSE) against the scatters
# they replace, and the hybrid path's gid_row sweep
# ---------------------------------------------------------------------------


def _routes_while(fn):
    """(result, {route counter: times taken}) while ``fn`` traces."""
    from repro.core import telemetry

    before = telemetry.registry().counters()
    with dispatch.overrides(enable_trace=True):
        out = fn()
    after = telemetry.registry().counters()
    return out, {k: v - before.get(k, 0) for k, v in after.items()
                 if k.startswith("route.") and v > before.get(k, 0)}


def _scatter_reference(reduce, values, ids, g):
    """The XLA scatter each dense reduction replaces."""
    if reduce == "sum":
        return jnp.zeros((g,), values.dtype).at[ids].add(values, mode="drop")
    if jnp.issubdtype(values.dtype, jnp.floating):
        ident = jnp.inf if reduce == "min" else -jnp.inf
    else:
        info = jnp.iinfo(values.dtype)
        ident = info.max if reduce == "min" else info.min
    at = jnp.full((g,), ident, values.dtype).at[ids]
    return (at.min if reduce == "min" else at.max)(values, mode="drop")


GD = dispatch.G_DENSE


@pytest.fixture
def tpu_forms(monkeypatch):
    """Route as on the TPU, where XLA fuses the dense form; this backend
    runs the same jnp program, at test sizes."""
    monkeypatch.setattr(dispatch, "_dense_fuses", lambda: True)


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
def test_backend_without_fused_dense_form_keeps_scatter(rng, reduce):
    """XLA:CPU builds the (G, rows) compare in memory, so off the TPU a
    small table keeps the scatter, with the same answer."""
    path = "dense" if jax.default_backend() == "tpu" else "xla_scatter"
    ids = rng.integers(0, 9, 1500).astype(np.int32)
    values = rng.integers(-1000, 1000, 1500).astype(np.int32)
    fn = {"sum": dispatch.segment_sum, "min": dispatch.segment_min,
          "max": dispatch.segment_max}[reduce]
    got, routes = _routes_while(
        lambda: fn(jnp.asarray(values), jnp.asarray(ids), 8))
    want = _scatter_reference(reduce, jnp.asarray(values), jnp.asarray(ids), 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert routes == {f"route.segment_{reduce}.{path}": 1}, routes


@pytest.mark.usefixtures("tpu_forms")
@pytest.mark.parametrize("rows", [0, 1500])
@pytest.mark.parametrize("g", [1, GD, GD + 1])
@pytest.mark.parametrize("reduce,dtype", [
    ("sum", np.int32), ("min", np.int32), ("max", np.int32),
    ("min", np.float32), ("max", np.float32)])
def test_dense_segment_reduce_matches_scatter(rng, reduce, dtype, g, rows):
    """int32 sums (COUNT, the presence count), int32 mins (``rep``) and
    float/int min and max: the dense form is the scatter bit for bit, with
    padding (id == G) and ids past it dropped; the route counter says which
    form ran, on both sides of G_DENSE."""
    ids = rng.integers(0, g + 3, rows).astype(np.int32)
    if dtype == np.int32:
        values = rng.integers(-1000, 1000, rows).astype(np.int32)
    else:
        values = rng.standard_normal(rows).astype(np.float32)
    fn = {"sum": dispatch.segment_sum, "min": dispatch.segment_min,
          "max": dispatch.segment_max}[reduce]
    got, routes = _routes_while(
        lambda: fn(jnp.asarray(values), jnp.asarray(ids), g))
    want = _scatter_reference(reduce, jnp.asarray(values), jnp.asarray(ids), g)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    path = "dense" if g <= GD else "xla_scatter"
    assert routes == {f"route.segment_{reduce}.{path}": 1}, routes


@pytest.mark.usefixtures("tpu_forms")
@pytest.mark.parametrize("domain", [1, GD, GD + 1])
def test_unique_bounded_presence_count_both_forms(rng, domain):
    """The presence count of ``unique_bounded`` agrees with the argsort
    unique on both sides of G_DENSE, padding slots and empty input
    included."""
    for n in (0, 3000):
        a = rng.integers(0, domain, n).astype(np.int32)
        valid = rng.random(n) > 0.2
        (u, inv, ng), routes = _routes_while(lambda: P.unique_bounded(
            jnp.asarray(a), jnp.asarray(valid), domain, cap_groups=domain))
        want = np.unique(a[valid])
        assert int(ng) == len(want)
        np.testing.assert_array_equal(np.asarray(u)[:len(want)], want)
        np.testing.assert_array_equal(np.asarray(inv)[valid],
                                      np.searchsorted(want, a[valid]))
        path = "dense" if domain <= GD else "xla_scatter"
        assert routes == {f"route.segment_sum.{path}": 1}, routes


@pytest.mark.usefixtures("tpu_forms")
@pytest.mark.parametrize("cap", [GD, GD + 1])
def test_groupby_identical_across_dense_boundary(rng, cap):
    """A whole group-by (keys from ``rep``, COUNT, min, max) with the group
    table on either side of G_DENSE equals the numpy oracle."""
    n = 3000
    keys = rng.integers(0, 300, n).astype(np.int32)
    vals = rng.standard_normal(n).astype(np.float32)
    sel = rng.random(n) > 0.3
    cols = {"k": E.make_plain(keys), "v": E.make_plain(vals)}
    specs = [("c", "count", None), ("mn", "min", "v"), ("mx", "max", "v")]
    r, routes = _routes_while(lambda: G.groupby_aggregate(
        cols, ["k"], specs, num_groups_cap=cap, mask=E.make_plain_mask(sel),
        key_domains={"k": compress.column_domain(keys)}))
    path = "dense" if cap <= GD else "xla_scatter"
    assert {f"route.segment_min.{path}", f"route.segment_max.{path}",
            f"route.segment_sum.{path}"} <= set(routes), routes
    uk = np.unique(keys[sel])
    ng = int(r.num_groups)
    assert ng == len(uk)
    np.testing.assert_array_equal(np.asarray(r.keys["k"])[:ng], uk)
    ks, vs = keys[sel], vals[sel]
    np.testing.assert_array_equal(np.asarray(r.aggs["c"])[:ng],
                                  [np.sum(ks == k) for k in uk])
    np.testing.assert_array_equal(np.asarray(r.aggs["mn"])[:ng],
                                  [vs[ks == k].min() for k in uk])
    np.testing.assert_array_equal(np.asarray(r.aggs["mx"])[:ng],
                                  [vs[ks == k].max() for k in uk])


def _gid_per_row_by_gather(view, gid, nrows):
    """The row -> segment -> group gather the sweep replaced."""
    seg = E._run_id_per_row(view.starts, view.n, nrows)
    return gid[jnp.clip(seg, 0, gid.shape[0] - 1)]


def _gapped_rle(vals, keep):
    """RLE column of ``vals`` whose runs cover only the rows in ``keep``."""
    v, s, e = [], [], []
    i, n = 0, len(vals)
    while i < n:
        if not keep[i]:
            i += 1
            continue
        j = i
        while j + 1 < n and keep[j + 1] and vals[j + 1] == vals[i]:
            j += 1
        v.append(vals[i])
        s.append(i)
        e.append(j)
        i = j + 1
    return E.make_rle(np.asarray(v, vals.dtype), s, e, n,
                      capacity=len(v) + 4)


@pytest.mark.parametrize("menc", [None, "rle", "index"])
@pytest.mark.parametrize("kenc", ["rle", "gapped_rle", "index"])
def test_hybrid_gid_row_sweep_identical_to_gather(rng, monkeypatch, kenc,
                                                  menc):
    """Hybrid path (RLE or Index keys, Plain aggregates, gaps in keys and
    mask): the GroupByResult with the gid_row sweep is bit-identical to
    the one with the old row-sized gather."""
    n = 700
    keys = np.sort(rng.integers(0, 6, n)).astype(np.int32)
    vals = rng.standard_normal(n).astype(np.float32)
    keep = rng.random(n) > 0.15
    if kenc == "rle":
        kcol = make_rle_col(keys)
    elif kenc == "gapped_rle":
        kcol = _gapped_rle(keys, keep)
    else:
        pos = np.nonzero(keep)[0]
        kcol = E.make_index(keys[pos], pos, nrows=n, capacity=len(pos) + 4)
    cols = {"k": kcol, "v": E.make_plain(vals)}
    mask = MASK_ENCODERS[menc](rng.random(n) > 0.3) if menc else None
    specs = SPECS + [("sd", "std", "v"), ("mn", "min", "v")]

    def run():
        return G.groupby_aggregate(
            cols, ["k"], specs, num_groups_cap=8, mask=mask,
            key_domains={"k": compress.column_domain(keys)})

    swept = run()
    monkeypatch.setattr(G, "_gid_per_row", _gid_per_row_by_gather)
    _assert_identical(swept, run())

"""The direct-address PK-FK probe against the sorted one (DESIGN.md §6).

When the fact FK has an ingest domain of at most
``join.DIRECT_MAP_MAX_SLOTS`` slots, ``Query._prepare_join_side`` builds a
map over it and ``join.pk_fk_join`` probes with one gather; otherwise it
binary-searches the sorted build side. Both must give the same mask and
the same gathered payloads, bit for bit, in the fact key's own encoding.
Also: the ``join_side`` span, the ``join.probe.*`` and ``join.map_bytes``
counters and ``last_stats["join_prep_ms"]``.
"""
import jax
import numpy as np
import pytest

from repro.core import arithmetic, compress, join as join_mod, telemetry
from repro.core.encodings import decode_column, decode_mask
from repro.core.partition import PartitionedQuery, PartitionedTable
from repro.core.plan import Query, col
from repro.core.table import Table
from repro.kernels import dispatch

CFG = compress.CompressionConfig(plain_threshold=1000)
N_DIM = 300


def _fact(rng, n=6000):
    """FK in runs (RLE-able), reaching past both ends of the dimension's
    keys 100..399; a measure beside it."""
    runs = rng.integers(1, 6, n)
    keys = rng.integers(60, 460, n)
    fk = np.repeat(keys, runs)[:n].astype(np.int32)
    return {"fk": fk, "v": rng.random(n).astype(np.float32)}


def _dim(rng):
    keys = np.arange(100, 100 + N_DIM, dtype=np.int32)
    return Table.from_arrays({
        "pk": rng.permutation(keys),  # stored out of key order
        "a": rng.integers(0, 9, N_DIM).astype(np.int32),
        "b": rng.random(N_DIM).astype(np.float32),
        "s": np.array([f"S{i % 11}" for i in range(N_DIM)]),
    }, cfg=CFG)


WHERE = {"none": col("a") > 100, "some": col("a") < 4, "all": None}


def _sides(q, op, bound, monkeypatch):
    """The build side of ``op`` prepared under ``bound`` slots."""
    monkeypatch.setattr(join_mod, "DIRECT_MAP_MAX_SLOTS", bound)
    return q._prepare_join_side(op)


def _probe(col_, side):
    return jax.jit(lambda c, s: join_mod.pk_fk_join(c, *s))(col_, side)


def _assert_same(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("where", sorted(WHERE))
@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
@pytest.mark.parametrize("enc", ["plain", "rle", "index"])
def test_direct_probe_matches_sorted_bit_for_bit(rng, enc, packed, where,
                                                 monkeypatch):
    t = Table.from_arrays(_fact(rng), cfg=CFG, encodings={"fk": enc},
                          pack=packed)
    q = Query(t).join(_dim(rng), fk="fk", on="pk", cols=["a", "b", "s"],
                      where=WHERE[where])
    op = q.ops[0]
    direct = _sides(q, op, 1 << 22, monkeypatch)
    ordered = _sides(q, op, 0, monkeypatch)
    assert isinstance(direct[0], join_mod.DirectMap)
    assert not isinstance(ordered[0], join_mod.DirectMap)
    col_ = t.columns["fk"]
    got, want = _probe(col_, direct), _probe(col_, ordered)
    _assert_same(got, want)
    hits = int(np.asarray(decode_mask(got[0])).sum())
    if where == "none":
        assert hits == 0
    else:
        assert 0 < hits < t.nrows  # keys past the dimension's range miss


def test_dictionary_fk_probes_directly_over_the_code_space(rng, monkeypatch):
    universe = np.array([f"K{i:03d}" for i in range(80)])
    fact = {"k": np.sort(rng.choice(universe, 4000)),
            "v": rng.random(4000).astype(np.float32)}
    t = Table.from_arrays(fact, cfg=CFG)
    assert t.domains["k"] == (0, len(t.dictionaries["k"]))
    dim_keys = np.array([f"K{i:03d}" for i in range(0, 120, 3)])
    dim = Table.from_arrays({"k": dim_keys,
                             "w": np.arange(len(dim_keys), dtype=np.int32)},
                            cfg=CFG)
    q = Query(t).join(dim, fk="k", cols=["w"], where=col("w") < 30)
    op = q.ops[0]
    direct = _sides(q, op, 1 << 22, monkeypatch)
    ordered = _sides(q, op, 0, monkeypatch)
    assert isinstance(direct[0], join_mod.DirectMap)
    assert direct[0].rows.shape == (len(t.dictionaries["k"]),)
    _assert_same(_probe(t.columns["k"], direct),
                 _probe(t.columns["k"], ordered))
    monkeypatch.undo()
    r = (Query(t).join(dim, fk="k", cols=["w"], where=col("w") < 30)
         .aggregate({"c": ("count", None), "sw": ("sum", "w")}).run())
    w_of = {k: i for i, k in enumerate(dim_keys) if i < 30}
    want = [w_of[k] for k in fact["k"] if k in w_of]
    assert int(r["c"]) == len(want)
    assert int(float(r["sw"])) == sum(want)


@pytest.mark.parametrize("past", [0, 1], ids=["at_bound", "one_past"])
def test_domain_past_the_bound_takes_the_sorted_route(past):
    """A fact FK of ``DIRECT_MAP_MAX_SLOTS + past`` slots: at the bound it
    is mapped, one slot past it keeps the sorted build side."""
    size = join_mod.DIRECT_MAP_MAX_SLOTS + past
    fact = {"fk": np.array([7, 7 + size - 1, 9, 7], np.int32),
            "v": np.ones(4, np.float32)}
    t = Table.from_arrays(fact, cfg=CFG)
    assert t.domains["fk"] == (7, size)
    dim = Table.from_arrays({"pk": np.array([7, 9, 12], np.int32),
                             "w": np.array([1, 10, 100], np.int32)}, cfg=CFG)
    q = Query(t).join(dim, fk="fk", on="pk", cols=["w"])
    side = q._prepare_join_side(q.ops[0])[0]
    assert isinstance(side, join_mod.DirectMap) == (past == 0)
    r = q.aggregate({"c": ("count", None), "sw": ("sum", "w")}).run()
    assert int(r["c"]) == 3 and int(float(r["sw"])) == 12


def test_float_fk_keeps_the_sorted_route():
    fact = {"fk": np.array([1.5, 2.5, 2.5, 9.0], np.float32),
            "v": np.ones(4, np.float32)}
    t = Table.from_arrays(fact, cfg=CFG)
    dim = Table.from_arrays({"pk": np.array([2.5, 9.0], np.float32),
                             "w": np.array([3, 4], np.int32)}, cfg=CFG)
    before = telemetry.registry().counters()
    r = (Query(t).join(dim, fk="fk", on="pk", cols=["w"])
         .aggregate({"c": ("count", None), "sw": ("sum", "w")}).run())
    after = telemetry.registry().counters()
    assert int(r["c"]) == 3 and int(float(r["sw"])) == 10
    assert after.get("join.probe.sorted", 0) - before.get(
        "join.probe.sorted", 0) == 1
    assert after.get("join.probe.direct", 0) == before.get(
        "join.probe.direct", 0)


def _rebound_fk_query(rng, how):
    """A join whose FK name an earlier op rebinds: a ``map`` that shifts
    the keys past their ingest domain, or a join that gathers another
    dimension's column under the FK's name."""
    fact = _fact(rng)
    fact["g"] = rng.integers(0, 40, len(fact["fk"])).astype(np.int32)
    t = Table.from_arrays(fact, cfg=CFG, encodings={"fk": "rle"})
    q = Query(t)
    if how == "map":
        q = q.map("fk", lambda env: arithmetic.scalar_op(env["fk"], "add",
                                                          200))
        fk = fact["fk"] + 200
    else:
        hop = Table.from_arrays({"g": np.arange(40, dtype=np.int32),
                                 "fk": np.arange(40, dtype=np.int32) * 9
                                 + 90}, cfg=CFG)
        q = q.join(hop, fk="g", cols=["fk"])
        fk = fact["g"] * 9 + 90
    dim = _dim(rng)
    q = q.join(dim, fk="fk", on="pk", cols=["a"]).aggregate(
        {"c": ("count", None), "sa": ("sum", "a")})
    a_of = dict(zip(np.asarray(decode_column(dim.columns["pk"])).tolist(),
                    np.asarray(decode_column(dim.columns["a"])).tolist()))
    want = [a_of[k] for k in fk.tolist() if k in a_of]
    return q, want


@pytest.mark.parametrize("how", ["map", "join"])
def test_rebound_fk_probes_over_its_live_domain(rng, how, monkeypatch):
    """The map is built over the domain of the value the probe reads, not
    the fact column's ingest domain: a shifted FK has none and takes the
    sorted route; a gathered one takes its dimension's domain. Both
    answer as the sorted route does."""
    q, want = _rebound_fk_query(rng, how)
    op = q.ops[-2]
    side = q._prepare_join_side(op)[0]
    if how == "map":
        assert not isinstance(side, join_mod.DirectMap)
    else:
        assert isinstance(side, join_mod.DirectMap)
        assert (int(side.lo), int(side.hi)) == (90, 90 + 39 * 9)
    got = q.run()
    assert int(got["c"]) == len(want) > 0
    assert int(float(got["sa"])) == sum(want)
    monkeypatch.setattr(join_mod, "DIRECT_MAP_MAX_SLOTS", 0)
    ref, _ = _rebound_fk_query(np.random.default_rng(0), how)
    ref = ref.run()
    for k in ("c", "sa"):
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(ref[k]))


def _two_join_query(rng):
    fact = _fact(rng, 8000)
    fact["fk2"] = rng.integers(0, 50, 8000).astype(np.int32)
    pt = PartitionedTable.from_arrays(fact, cfg=CFG, partition_rows=2048,
                                      pack=True)
    dim2 = Table.from_arrays({"k2": np.arange(50, dtype=np.int32),
                              "c": (np.arange(50) % 3).astype(np.int32)},
                             cfg=CFG)
    q = (PartitionedQuery(pt)
         .join(_dim(rng), fk="fk", on="pk", cols=["a"], where=col("a") < 6)
         .join(dim2, fk="fk2", on="k2", cols=["c"])
         .groupby(["a", "c"], {"s": ("sum", "v")}, num_groups_cap=64))
    return pt, q


def test_counters_and_join_side_spans_per_execution(rng):
    pt, q = _two_join_query(rng)
    slots = pt.domains["fk"][1] + pt.domains["fk2"][1]
    telemetry.reset()
    with dispatch.overrides(enable_trace=True):
        for _ in range(2):
            q.run()
    c = telemetry.registry().counters()
    assert c["join.probe.direct"] == 4 and "join.probe.sorted" not in c
    assert c["join.map_bytes"] == 2 * 4 * slots
    spans = telemetry.registry().events(qid=q.qid, name="join_side")
    assert len(spans) == 4
    prepare = {e["id"] for e in telemetry.registry().events(
        qid=q.qid, name="prepare")}
    assert {e["parent"] for e in spans} <= prepare and len(prepare) == 2
    assert {e["attrs"]["route"] for e in spans} == {"direct"}
    assert sorted(e["attrs"]["slots"] for e in spans) == sorted(
        [pt.domains["fk"][1], pt.domains["fk2"][1]] * 2)
    assert {e["attrs"]["dim"] for e in spans} == {"pk", "k2"}
    last_two = sorted(spans, key=lambda e: e["ts"])[2:]
    ms = sum(e["dur"] for e in last_two) * 1e3
    assert q.last_stats["join_prep_ms"] == pytest.approx(ms, abs=2e-3)


def test_join_prep_ms_is_kept_untraced(rng):
    _, q = _two_join_query(rng)
    before = telemetry.registry().counters()
    q.run()
    after = telemetry.registry().counters()
    assert q.last_stats["join_prep_ms"] > 0
    assert after["join.probe.direct"] - before.get("join.probe.direct",
                                                   0) == 2


def test_direct_and_sorted_routes_answer_alike_end_to_end(rng, monkeypatch):
    _, q = _two_join_query(rng)
    got = q.run()
    monkeypatch.setattr(join_mod, "DIRECT_MAP_MAX_SLOTS", 0)
    _, q2 = _two_join_query(np.random.default_rng(0))
    want = q2.run()
    for k in got.keys:
        np.testing.assert_array_equal(got.keys[k], want.keys[k])
    for a in got.aggs:
        np.testing.assert_array_equal(got.aggs[a], want.aggs[a])

"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX, so the kernels dispatch routes on
the TPU and the query programs of the engine's main path are compiled here
exactly as the chip would compile them, under the on-chip policy (Pallas
on, interpret mode off). Nothing runs: these tests catch what Mosaic and
XLA:TPU refuse — unaligned blocks, gathers with no lowering, VMEM
overruns — which interpret-mode tests cannot see. Kernels compile at
partition size (2^22 rows) and at their VMEM upper bounds.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
only the worker that runs this file may try.
"""
import os
import re
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import compress, telemetry
from repro.core.partition import (PartitionedQuery, PartitionedTable,
                                  base_masked_program)
from repro.core.plan import Query, col
from repro.core.table import Table
from repro.kernels import dispatch
from repro.kernels.bucketize import bucketize_count_kernel
from repro.kernels.segment_reduce import segment_sum_kernel
from repro.kernels.topk import MAX_KERNEL_K, topk_kernel
from repro.kernels.unpack import unpack_kernel

from benchmarks.bench_production import _semi_keys, make_star

PARTITION_ROWS = 1 << 22
QUERY_ROWS = 1 << 17


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler logs under /tmp unless told otherwise
    old_log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        if old_log_dir == "disabled":
            os.environ.pop("TPU_LOG_DIR", None)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a),
                                       sharding=sharding), tree)


def _compile_text(fn, args, sharding) -> str:
    # trace as on the chip: Pallas on, and the dense small-table reductions
    # the TPU backend takes
    with dispatch.overrides(use_pallas=True, interpret=False), \
            mock.patch.object(dispatch, "_dense_fuses", lambda: True):
        return jax.jit(fn).lower(*_shapes(args, sharding)).compile().as_text()


def _kernel_case(name):
    n = PARTITION_ROWS
    i32 = np.zeros((n,), np.int32)
    f32 = np.zeros((n,), np.float32)
    if name.startswith("unpack"):
        b = int(name.split("_b")[1])
        return (lambda w, off: unpack_kernel(w, b, off, n),
                (np.zeros((n * b // 32,), np.uint32), np.int32(0)))
    if name.startswith("count"):
        bounds = np.zeros((dispatch.COUNT_KERNEL_MAX_BOUNDARIES,),
                          i32.dtype if name.endswith("int32") else f32.dtype)
        queries = i32 if name.endswith("int32") else f32
        return (lambda b, q: bucketize_count_kernel(b, q), (bounds, queries))
    if name.startswith("segsum"):
        g = int(name.split("_g")[1])
        return (lambda v, ids: segment_sum_kernel(v, ids, g), (f32, i32))
    k = int(name.split("_k")[1].split("_")[0])
    return (lambda v: topk_kernel(v, k),
            (i32 if name.endswith("int32") else f32,))


@pytest.mark.parametrize("name", [
    # unpack streams word tiles: b=32 is its widest block
    "unpack_b1", "unpack_b7", "unpack_b24", "unpack_b32",
    # the counting bucketize at its boundary bound
    "count_int32", "count_float32",
    # segment_sum at a typical group count and at its VMEM bound
    "segsum_g128", f"segsum_g{dispatch.MAX_MATMUL_SEGMENTS}",
    # top-k at a typical k and at its bound
    "topk_k10_float32", f"topk_k{MAX_KERNEL_K}_float32",
    f"topk_k{MAX_KERNEL_K}_int32",
])
def test_routed_kernel_compiles(one_chip, name):
    fn, args = _kernel_case(name)
    assert "tpu_custom_call" in _compile_text(fn, args, one_chip)


def _star(n, seed=0):
    return make_star(np.random.default_rng(seed), n)


def _routes_while(fn):
    """Kernel routes dispatch took while ``fn`` traced (trace-time)."""
    before = telemetry.registry().counters()
    with dispatch.overrides(enable_trace=True):
        out = fn()
    after = telemetry.registry().counters()
    taken = {k for k, v in after.items()
             if k.startswith("route.") and v > before.get(k, 0)}
    return out, taken


QUERIES = {
    "filter_groupby": (
        lambda q: q.filter(col("c1") < 8).groupby(
            ["c0"], {"s": ("sum", "measure"), "c": ("count", None)},
            num_groups_cap=8),
        {"route.segment_sum.kernel"}),
    "semijoin_aggregate": (
        lambda q: q.semi_join("c4", _semi_keys(np.random.default_rng(1),
                                               1000, 0.5)).aggregate(
            {"s": ("sum", "measure"), "c": ("count", None)}),
        {"route.segment_sum.kernel"}),
    "topk": (
        lambda q: q.filter(col("c1") < 8).order_by("measure",
                                                   descending=True, limit=10),
        {"route.topk.kernel"}),
}


@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
@pytest.mark.parametrize("shape", sorted(QUERIES))
def test_query_program_compiles(one_chip, shape, packed):
    table = Table.from_arrays(
        _star(QUERY_ROWS), cfg=compress.CompressionConfig(plain_threshold=1000),
        pack=packed)
    stage, kernels = QUERIES[shape]
    q = stage(Query(table))
    args = (table.columns, tuple(q._prepare_inputs()))
    text, routes = _routes_while(
        lambda: _compile_text(q.build(), args, one_chip))
    assert kernels <= routes, routes
    assert "tpu_custom_call" in text


def test_production_q1_partition_program_compiles(one_chip):
    """The paper's production Q1 template (7 semi-joins, a PK-FK join, a
    SUM group-by), as the streamed executor compiles it for one packed
    partition of 2^22 rows."""
    rng = np.random.default_rng(2)
    table = PartitionedTable.from_arrays(
        _star(PARTITION_ROWS), cfg=compress.CompressionConfig(
            plain_threshold=1000), partition_rows=PARTITION_ROWS, pack=True)
    dim = Table.from_arrays({
        "c6": np.arange(16000, dtype=np.int32),
        "d6_cat": (np.arange(16000, dtype=np.int32) % 97).astype(np.int32),
    }, cfg=compress.CompressionConfig(plain_threshold=1000))
    q = PartitionedQuery(table)
    for c, card in {"c2": 64, "c3": 256, "c4": 1000, "c5": 4000, "c8": 50,
                    "c9": 200, "c11": 30}.items():
        q = q.semi_join(c, _semi_keys(rng, card, 0.5))
    q = q.join(dim, fk="c6", cols=["d6_cat"]).groupby(
        ["d6_cat"], {"s": ("sum", "measure"), "c": ("count", None)},
        num_groups_cap=128)
    args = (table.partitions[0].table.columns, tuple(q._prepare_inputs()),
            np.int32(PARTITION_ROWS))
    text, routes = _routes_while(lambda: _compile_text(
        base_masked_program(q.build(partial=True)), args, one_chip))
    assert {"route.segment_sum.kernel", "route.bucketize.count_kernel",
            "route.unpack.kernel"} <= routes, routes
    assert "tpu_custom_call" in text


def _row_indexed_ops(text: str, rows: int):
    """The scatters and gathers of a compiled HLO module whose index operand
    (operand 1) has ``rows`` in its shape, as ``(op, line)`` pairs."""
    define = re.compile(r"^\s*(?:ROOT )?%?(\S+) = (\S+) ([\w\-]+)\((.*)")
    found, shapes = [], {}
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            shapes = {}  # a new computation: its own operand names
            continue
        m = define.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        shapes[name] = shape
        if op in ("scatter", "gather"):
            args = [a.strip().lstrip("%")
                    for a in rest.split(")", 1)[0].split(",")]
            dims = re.findall(r"\d+", shapes.get(args[1], "").split("{")[0])
            if str(rows) in dims:
                found.append((op, line.strip()[:200]))
    return found


def _q1_partition(form: str, rows: int):
    """A TPC-H lineitem partition of ``rows`` rows, sorted on l_shipdate.
    ``row_level``: l_returnflag random R/A (Plain), as before the spec's
    CURRENTDATE; ``hybrid``: l_returnflag and l_linestatus in long runs
    (RLE), as after it."""
    rng = np.random.default_rng(14)
    ship = np.sort(rng.integers(9000, 9090, rows)).astype(np.int32)
    if form == "row_level":
        flag = np.where(rng.integers(0, 2, rows) == 0, "R", "A")
        status = np.full(rows, "F")
    else:
        flag = np.where(np.arange(rows) < rows // 8, "A", "N")
        status = np.where(ship > 9030, "O", "F")
    return {
        "l_shipdate": ship,
        "l_returnflag": flag,
        "l_linestatus": status,
        "l_quantity": rng.integers(1, 51, rows).astype(np.int32),
        "l_extendedprice": (rng.random(rows) * 1e5).astype(np.float32),
        "l_discount": (rng.integers(0, 11, rows) / 100).astype(np.float32),
        "l_tax": (rng.integers(0, 9, rows) / 100).astype(np.float32),
    }


@pytest.mark.parametrize("form", ["row_level", "hybrid"])
def test_q1_partition_program_has_no_row_sized_scatter_or_gather(one_chip,
                                                                form):
    """TPC-H Q1's group-by over one packed 2^20-row partition, in its
    row-level form (a Plain key) and its hybrid form (RLE keys, Plain
    aggregates): the TPU program indexes no small table row by row. The
    presence count, the rank lookup, ``rep`` and COUNT reduce densely;
    the hybrid path's row -> group ids come from a sweep."""
    from repro.core.arithmetic import binary_op
    from repro.core.encodings import PlainColumn, RLEColumn

    rows = 1 << 20
    table = PartitionedTable.from_arrays(
        _q1_partition(form, rows), cfg=compress.CompressionConfig(),
        partition_rows=rows, pack=True)
    cols = table.partitions[0].table.columns
    key_enc = (PlainColumn if form == "row_level" else RLEColumn)
    assert isinstance(cols["l_returnflag"], key_enc)
    assert isinstance(cols["l_linestatus"], RLEColumn)
    q = (PartitionedQuery(table)
         .filter(col("l_shipdate") <= 9060)
         .map("disc_price", lambda env: binary_op(
             env["l_extendedprice"], env["l_discount"],
             lambda e, d: e * (1 - d)))
         .map("charge", lambda env: binary_op(
             env["disc_price"], env["l_tax"], lambda p, t: p * (1 + t)))
         .groupby(["l_returnflag", "l_linestatus"],
                  {"sum_qty": ("sum", "l_quantity"),
                   "sum_base_price": ("sum", "l_extendedprice"),
                   "sum_disc_price": ("sum", "disc_price"),
                   "sum_charge": ("sum", "charge"),
                   "avg_qty": ("avg", "l_quantity"),
                   "avg_price": ("avg", "l_extendedprice"),
                   "avg_disc": ("avg", "l_discount"),
                   "count_order": ("count", None)}, num_groups_cap=8))
    args = (cols, tuple(q._prepare_inputs()), np.int32(rows))
    text, routes = _routes_while(lambda: _compile_text(
        base_masked_program(q.build(partial=True)), args, one_chip))
    assert {"route.segment_sum.dense", "route.segment_min.dense",
            "route.segment_sum.kernel"} <= routes, routes
    assert "tpu_custom_call" in text
    assert _row_indexed_ops(text, rows) == []


def _while_ops(text: str) -> int:
    return len(re.findall(r"\swhile\(", text))


def _ssb_join_partition(case: str, rows: int):
    """One packed 2^20-row lineorder partition and the dimension it probes:
    ``custkey``, an RLE ``lo_custkey`` (an order's lines share it) against
    the 60,000-row customer side; ``partkey``, a Plain ``lo_partkey``
    against the 400,000-row part side."""
    rng = np.random.default_rng(15)
    n_dim = 60_000 if case == "custkey" else 400_000
    if case == "custkey":
        fk = np.repeat(rng.integers(1, n_dim + 1, rows), 4)[:rows]
    else:
        fk = rng.integers(1, n_dim + 1, rows)
    fact = {"fk": fk.astype(np.int32),
            "v": rng.integers(0, 10_000, rows).astype(np.int32)}
    enc = {"fk": "rle" if case == "custkey" else "plain"}
    table = PartitionedTable.from_arrays(fact, cfg=compress.CompressionConfig(),
                                         partition_rows=rows, pack=True,
                                         encodings=enc)
    dim = Table.from_arrays({"pk": np.arange(1, n_dim + 1, dtype=np.int32),
                             "g": rng.integers(0, 5, n_dim).astype(np.int32)})
    return table, dim


@pytest.mark.parametrize("case", ["custkey", "partkey"])
def test_direct_join_probe_leaves_no_while_loop(one_chip, case, monkeypatch):
    """A SUM under one PK-FK join, compiled for one partition on both
    routes: the sorted build side's binary search is a ``while`` loop, the
    direct map's probe one gather and no loop. The RLE key keeps two
    loops of its own: the intersection of the join's run mask with the
    one-run base mask (``primitives`` range intersection) searches the
    base run's two bounds in the mask's run lists, one query each."""
    from repro.core import join as join_mod
    from repro.core.encodings import PlainColumn, RLEColumn

    rows = 1 << 20
    table, dim = _ssb_join_partition(case, rows)
    cols = table.partitions[0].table.columns
    assert isinstance(cols["fk"],
                      RLEColumn if case == "custkey" else PlainColumn)
    whiles = {}
    for route, bound in (("direct", join_mod.DIRECT_MAP_MAX_SLOTS),
                         ("sorted", 0)):
        monkeypatch.setattr(join_mod, "DIRECT_MAP_MAX_SLOTS", bound)
        q = (PartitionedQuery(table)
             .join(dim, fk="fk", on="pk", cols=[], where=col("g") < 2)
             .aggregate({"s": ("sum", "v")}))
        args = (cols, tuple(q._prepare_inputs()), np.int32(rows))
        assert isinstance(args[1][0][0], join_mod.DirectMap) == (
            route == "direct")
        text, routes = _routes_while(lambda: _compile_text(
            base_masked_program(q.build(partial=True)), args, one_chip))
        assert ("route.join_probe.direct" in routes) == (route == "direct")
        whiles[route] = _while_ops(text)
    assert whiles["direct"] == whiles["sorted"] - 1, whiles
    assert whiles["direct"] == (2 if case == "custkey" else 0), whiles

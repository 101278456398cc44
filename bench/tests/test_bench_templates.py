"""Every template of each configuration, run through the engine at a tiny
size on the CPU, matches the configuration's float64 numpy reference; the
bfloat16 control does not."""
import os
import re

import pytest

import _paths  # noqa: F401
import check
import harness

SMALL = {"scale_factor": 0.002, "partition_rows": 1 << 12}
# every configuration under bench/configs, whether a cell uses it yet or not
CONFIGS = sorted(os.path.splitext(f)[0] for f in
                 os.listdir(os.path.join(harness.BENCH, "configs"))
                 if f.endswith(".json"))


def _config(name):
    cfg, mod = harness.load_config(os.path.join("bench", "configs",
                                                name + ".json"))
    return mod, dict(cfg, **SMALL)


_DATA = {}


def _ingested(name):
    if name not in _DATA:
        mod, cfg = _config(name)
        data = mod.generate(987654321012, cfg)
        table, dims = harness.ingest(data, cfg)
        _DATA[name] = (mod, cfg, data, table, dims)
    return _DATA[name]


def _cases():
    for name in CONFIGS:
        mod, cfg = _config(name)
        for t in cfg["templates"]:
            yield name, t


@pytest.mark.parametrize("config,template", list(_cases()))
def test_template_matches_reference(config, template):
    from repro.core.partition import PartitionedQuery

    mod, cfg, data, table, dims = _ingested(config)
    stage = mod.templates(harness.engine_api())[template]
    result = stage(PartitionedQuery(table), dims).run()
    keys = [] if isinstance(result, dict) else list(result.keys)
    answer = harness.answer_of(result, table, dims, keys)
    ref = mod.reference(template, data)
    bad, gap = check.diff(answer, ref)
    assert not bad, (answer, ref)
    assert gap <= cfg["check"]["max_rel_err"]


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_bfloat16_control_fails_the_limit(config):
    """The control, in the program's place for every template, reads
    ``correct`` false, with room: over three times the limit."""
    mod, cfg, data, _, _ = _ingested(config)
    checks = check.control(mod, data, list(cfg["templates"]), cfg["check"])
    assert not check.verdict(checks)
    assert (checks["exact_mismatch"]["value"] > 0
            or checks["max_rel_err"]["value"]
            > 3 * cfg["check"]["max_rel_err"]), checks


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_reference_imports_nothing_of_the_engine(config):
    path = os.path.join(harness.BENCH, "configs", config + ".py")
    src = open(path).read()
    assert not re.search(r"^\s*(import|from)\s+repro", src, re.M)
    assert "src/repro" not in src


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_generator_is_deterministic_per_seed(config):
    mod, cfg = _config(config)
    a = mod.generate(2 ** 33 + 5, cfg)["fact"]
    b = mod.generate(2 ** 33 + 5, cfg)["fact"]
    c = mod.generate(2 ** 33 + 6, cfg)["fact"]
    assert all((a[k] == b[k]).all() for k in a)
    assert any(len(a[k]) != len(c[k]) or (a[k] != c[k]).any() for k in a)

"""``BENCHMARK.json`` and the files it names agree."""
import json
import re

import pytest

from cbench import counts, reference, spec
from conftest import BENCH, ROOT

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
CELLS = [w["name"] for w in B["workloads"]]


def test_names_and_bounds():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in B[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.fullmatch(n) for n in names)
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert B["run_seconds"] <= 51 and B["paths"] == ["chipbench"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    c = spec.load(ROOT, cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.reader(ROOT, m["name"]))
    assert c.config["model"]["vocab"] > 0 and c.mix["max_len"] % 256 == 0
    assert set(c.limits) == {"token_gap_mean", "sample", "min_tokens"}


def test_every_metric_file_is_named():
    """Each reader file serves some metric, and each metric finds one; a
    split name (``device.idle_share.decode``) shares its stem's file."""
    files = {p.name for p in (BENCH / "metrics").glob("*.py")}
    named = {m["name"] for m in B["end_to_end"] + B["per_layer"]}
    used = {spec.reader_path(ROOT, n).name for n in named}
    assert files == used
    assert spec.reader_path(ROOT, "device.idle_share.decode").name == "device.idle_share.py"
    with pytest.raises(FileNotFoundError):
        spec.reader_path(ROOT, "no.such.metric")


def test_traffic_names_its_source():
    for w in B["workloads"]:
        mix = spec.load(ROOT, w["name"]).mix
        assert mix["source"] and "\n" not in mix["source"]


def test_equations_files_name_configs():
    """``chipbench/equations/<config>.py`` belongs to a configuration of
    ``BENCHMARK.json`` and defines the whole interface."""
    configs = {c["name"] for c in B["configs"]}
    for path in sorted((BENCH / "equations").glob("*.py")):
        assert path.stem in configs
        spec.equations(ROOT, path.stem)       # raises where a function is missing


@pytest.mark.parametrize("config", ["stablelm-3b", "minicpm3-4b"])
def test_default_equations_are_the_yardstick(config):
    eq = spec.equations(ROOT, config)
    assert eq == spec.default_equations()
    assert eq.logits_at is reference.logits_at
    assert eq.settled_at is reference.settled_at
    for name in ("gemm_calls", "decode_attention_calls", "flash_attention_calls",
                 "model_flops_decode", "model_flops_prefill"):
        assert getattr(eq, name) is getattr(counts, name)
    for w in B["workloads"]:
        if w["config"] == config:
            assert spec.load(ROOT, w["name"]).equations == eq

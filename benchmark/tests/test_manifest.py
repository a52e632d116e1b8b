"""BENCHMARK.json against the contract, and the harness against its own
promise that a cell, a configuration, a traffic mix and a per-layer metric
are each new files plus one entry."""

import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import manifest, runner

ROOT = os.path.dirname(manifest.BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _shipped():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def doc(tmp_path_factory):
    doc = _shipped()
    root = tmp_path_factory.mktemp("shipped")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    doc["_root"] = str(root)
    return doc


def _book(doc):
    return manifest.Manifest(doc["_root"], manifest.BENCH_DIR,
                             files_root=ROOT)


def _metrics(doc):
    return doc["end_to_end"] + doc["per_layer"]


def test_keys_are_exactly_the_contracts(doc):
    assert set(doc) - {"_root"} == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_names_and_units_use_only_the_allowed_characters(doc):
    names = ([m["name"] for m in _metrics(doc)]
             + [w["name"] for w in doc["workloads"]]
             + [w["traffic"] for w in doc["workloads"]]
             + [c["name"] for c in doc["configs"]]
             + [k for c in doc["configs"] for k in c["reduced"]])
    for name in names:
        assert NAME.match(name), name
    for m in _metrics(doc):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for label in ("name",):
        for group in ("workloads", "configs"):
            seen = [e[label] for e in doc[group]]
            assert len(seen) == len(set(seen))
    seen = [m["name"] for m in _metrics(doc)]
    assert len(seen) == len(set(seen))
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    for text in ([w["why"] for w in doc["workloads"]]
                 + [c["why"] for c in doc["configs"]]
                 + [c["source"] for c in doc["configs"]]
                 + [m["layer"] for m in doc["per_layer"]] + doc["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_name_resolves_to_a_file(doc):
    book = _book(doc)
    for w in doc["workloads"]:
        cell = book.cell(w["name"])
        assert cell["config"]["cli"]["model_zoo"]
        manifest.load_named("generators", cell["traffic"]["generator"])
        for kind in ("reference", "opcounts"):
            manifest.load_named(kind, cell["config"][kind])
        for kernel in cell["config"]["kernels"]:
            manifest.load_named("kernels", kernel)
    for m in doc["per_layer"]:
        assert callable(book.reader(m["name"]))
    used = {w["config"] for w in doc["workloads"]}
    assert used == {c["name"] for c in doc["configs"]}
    for c in doc["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in doc["paths"]))
        with open(os.path.join(ROOT, c["file"])) as fh:
            assert json.load(fh)["reduced"] == c["reduced"]


def test_every_cell_reports_what_it_must(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in doc["workloads"]:
        mine = [m for m in doc["end_to_end"]
                if manifest.reports(m, w["name"])]
        assert len(mine) >= 2, w["name"]
        assert any(manifest.reports(m, w["name"])
                   for m in doc["per_layer"]), w["name"]
    cells = {w["name"] for w in doc["workloads"]}
    for m in doc["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells, (m["name"], cell)
            assert manifest.reports(e2e[m["moves"]], cell), (m["name"], cell)


def test_four_chip_cells_are_at_most_a_quarter_or_one(doc):
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in doc["workloads"])
    assert len(four) <= max(1, len(doc["workloads"]) // 4)


def test_bounds_are_shares_and_no_bound_is_per_cell(doc):
    for m in doc["end_to_end"]:
        assert isinstance(m["bound"], float) and 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", [])) <= {
            w["name"] for w in doc["workloads"]}
    assert all("bound" not in m for m in doc["per_layer"])
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(
        doc["run_seconds"], int)
    runs = 2 + 14 * 24
    assert (runs * (doc["run_seconds"] + 60) + 24 * 180 + 1200) <= 43200


def test_roofline_names_and_units(doc):
    for m in doc["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


def test_unknown_names_are_errors_that_list_what_exists():
    book = manifest.Manifest(ROOT)
    with pytest.raises(manifest.ManifestError, match="olmo1b.seq2048"):
        book.cell("no-such-cell")
    with pytest.raises(manifest.ManifestError, match="trainer.mfu"):
        book.reader("no.such.metric")
    with pytest.raises(manifest.ManifestError, match="tokens_zipf"):
        manifest.load_named("generators", "no_such_generator")


DUMMY_REFERENCE = """
import jax.numpy as jnp

TOLERANCE = 0.5
MICROBATCH = 1


def case(config, params, rng, key):
    tokens = jnp.asarray(rng.integers(
        0, config["vocab_size"], (MICROBATCH, config["seq_len"])), jnp.int32)
    # a reference of its own: the loss of uniform logits, ln(V)
    return params, tokens, tokens, lambda p: jnp.full(
        (MICROBATCH,), jnp.log(float(config["vocab_size"])))
"""


def test_a_cell_and_everything_it_names_are_added_as_files(tmp_path):
    """A temporary copy gains a cell, a configuration with its own
    reference, operation count and kernel, a traffic mix with its own
    generator, and a per-layer metric: no existing file is edited but
    BENCHMARK.json's lists, and the harness resolves and runs them."""
    root = tmp_path / "copy"
    shutil.copytree(manifest.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = _shipped()
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / "olmo1b.json").read_text())
    cfg = runner.merge(cfg, cfg.pop("rehearsal"))          # tiny sizes
    cfg.update(reference="dummy-ref", opcounts="dummy-ops",
               kernels=["dummy_kernel"])
    (bench / "configs" / "dummy.json").write_text(json.dumps(cfg))
    (bench / "reference" / "dummy-ref.py").write_text(DUMMY_REFERENCE)
    (bench / "opcounts" / "dummy-ops.py").write_text(
        "def train_flops(config):\n    return 197e12\n")
    (bench / "kernels" / "dummy_kernel.py").write_text(
        "PATTERN = 'custom-call'\n\n\ndef classify(results, operands):\n"
        "    return 'only', (197e12, 1)\n")
    (bench / "generators" / "dummy-gen.py").write_text(
        "def generate(out_dir, seed, records):\n"
        "    return 'dummy:%s:%d:%d' % (out_dir, seed, records)\n")
    mix = json.loads((bench / "traffic" / "tokens-b8.json").read_text())
    mix.update(generator="dummy-gen", params={"records": 3},
               params_from_config={})
    mix["flags"]["batch_size"] = 2
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (bench / "layers" / "dummy.metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    (bench / "layers" / "kernel.dummy_kernel_roofline.py").write_text(
        "from benchmark.lib import kernels\n\n\ndef read(run):\n"
        "    return kernels.roofline_share(run, 'dummy_kernel')\n")
    doc["configs"].append({"name": "dummy", "source": "test",
                           "file": "benchmark/configs/dummy.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "dummy.cell", "config": "dummy",
                             "traffic": "dummy-mix", "chips": 1,
                             "why": "test"})
    # records_per_s lists no workloads: every cell reports it, the new
    # one too
    for name in ("dummy.metric", "kernel.dummy_kernel_roofline"):
        doc["per_layer"].append({
            "name": name, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "test",
            "moves": "records_per_s", "workloads": ["dummy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    # in a process of its own, so that ``benchmark`` is the copy's package
    done = subprocess.run(
        [sys.executable, "-c", DRIVE_THE_COPY], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
            [str(root), ROOT])), capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got["batch_size"] == 2 and got["num_layers"] == 2
    assert "dummy.metric" in got["per_layer"]
    assert "trainer.collective_exposed_share" not in got["per_layer"]
    assert got["dummy.metric"] == 42.0
    assert got["origin"].startswith("dummy:") and got["origin"].endswith(
        ":7:3")
    # 197e12 operations a record x 0.5 records/s over one chip's 197e12
    assert got["trainer.mfu"] == pytest.approx(50.0)
    # one call of 197e12 operations took 2 s where 1 s is the least
    assert got["kernel.dummy_kernel_roofline"] == pytest.approx(50.0)
    # the shipped kernel's reader finds nothing in this configuration
    assert got["kernel.flash_attention_roofline"] is None
    assert got["compare"]["reference"] == "dummy-ref" and got["compare"]["ok"]
    assert got["compare"]["reference_loss"] == pytest.approx(
        math.log(256), rel=1e-6)


DRIVE_THE_COPY = """
import json, os, subprocess, sys, types
from benchmark.lib import datagen, manifest
root = os.getcwd()
assert manifest.BENCH_DIR == os.path.join(root, "benchmark")
book = manifest.Manifest(root)
cell = book.cell("dummy.cell")
run = types.SimpleNamespace(
    config=cell["config"], cell=cell, device={"kind": "TPU v5 lite"},
    window={"records_per_s": 0.5},
    trace={"custom_calls": {
        '%k = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target="x"':
        [2.0, 1.0]}})
out = {"batch_size": cell["traffic"]["flags"]["batch_size"],
       "num_layers": cell["config"]["cli"]["model_params"]["num_layers"],
       "per_layer": [m["name"] for m in cell["per_layer"]],
       "origin": datagen.ensure(os.path.join(root, "data"),
                                cell["traffic"]["generator"],
                                cell["traffic"]["params"], 7)}
for name in ("dummy.metric", "trainer.mfu", "kernel.dummy_kernel_roofline",
             "kernel.flash_attention_roofline"):
    out[name] = book.reader(name)(run)
done = subprocess.run(
    [sys.executable, os.path.join(root, "benchmark", "lib", "compare.py"),
     "--config-file", cell["config_file"], "--seed", "3"],
    capture_output=True, text=True)
assert done.returncode == 0, done.stderr[-3000:]
out["compare"] = json.loads(done.stdout.strip().splitlines()[-1])
print(json.dumps(out))
"""

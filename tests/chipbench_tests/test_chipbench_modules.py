"""The seam between the harness and an architecture: every configuration
names a ``reference`` and a ``counts`` module that load and export the
contract of ``chipbench/modules.py``, the counts without jax; a reference
module brings the serving half, the training half or both, and is asked
for the one its cell needs; and the harness's own files name no key or
leaf of any one architecture.
"""
import glob
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import modules  # noqa: E402

CONFIGS = sorted(glob.glob(os.path.join(ROOT, "chipbench", "configs",
                                        "*.json")))
#: the files that may know no architecture (ISSUE 28, tentpole 1)
HARNESS = sorted(
    [os.path.join("chipbench", f) for f in (
        "model_file.py", "serve_side.py", "run.py", "rehearse_compile.py",
        "check.py", "traffic.py", "serve_client.py", "work.py",
        "modules.py", "reduce.py")]
    + [os.path.relpath(p, ROOT) for p in glob.glob(
        os.path.join(ROOT, "chipbench", "metrics", "*.py"))])
ONE_ARCHITECTURE_S = ("num_key_value_heads", "intermediate_size",
                      "transformer_block", "wq", "w3", "swiglu")


def config(path):
    with open(path) as f:
        return json.load(f)


def stem(path):
    return os.path.basename(path)[:-len(".json")]


COUNTS_WITHOUT_JAX = """
import json, sys
sys.path.insert(0, %r)
from chipbench import modules, work
cfg = json.load(open(%r))
mod = modules.counts_of(cfg)
s = work.dims(cfg)
assert {"layers", "vocab", "window"} <= set(s), sorted(s)
assert s["layers"] == cfg["num_hidden_layers"]
assert s["vocab"] == cfg["vocab_size"]
assert work.model_params(cfg) >= work.matmul_params(cfg) > 0
assert work.token_flops(cfg, 16, 0) > work.token_flops(cfg, 16, 1) > 0
assert work.kv_bytes_per_token(cfg, 4) > 0 and work.weight_bytes(cfg, 2) > 0
assert "jax" not in sys.modules, "the counts imported jax"
print(mod.__name__)
"""


@pytest.mark.parametrize("path", CONFIGS, ids=stem)
def test_counts_load_without_jax(path):
    """In a process of its own, as the parent is one: the configuration's
    ``counts`` module and everything ``work.py`` derives from it."""
    out = subprocess.run(
        [sys.executable, "-c", COUNTS_WITHOUT_JAX % (ROOT, path)],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip()


HALVES = {"training": (modules.TRAINING, "the training half"),
          "serving": (modules.SERVING, "the serving half")}


def held_to_what_it_exports(cfg):
    """What the module exports decides which of ``reference_of(cfg,
    training=True)`` and ``reference_of(cfg, serving=True)`` raise, and the
    line names the half that is missing. Returns the halves it has."""
    mod = modules.reference_of(cfg)
    for name in modules.REFERENCE:
        assert callable(getattr(mod, name)), name
    has = []
    for flag, (names, words) in HALVES.items():
        if all(callable(getattr(mod, n, None)) for n in names):
            assert modules.reference_of(cfg, **{flag: True}) is mod
            has.append(flag)
        else:
            with pytest.raises(modules.ContractError,
                               match="exports no .*: " + words):
                modules.reference_of(cfg, **{flag: True})
    assert has, "a module may leave either half out, not both"
    return has


@pytest.mark.parametrize("path", CONFIGS, ids=stem)
def test_reference_module_exports_the_contract(path):
    cfg = config(path)
    held_to_what_it_exports(cfg)
    layers = modules.reference_of(cfg).layer_list(cfg)
    assert len([ly for ly in layers if ly["name"].startswith("blk")]) \
        == cfg["num_hidden_layers"]
    assert "solver" not in layers[0]
    assert all(ly["solver"] == "adam" and ly["learning_rate"] == 0.5
               for ly in modules.reference_of(cfg).layer_list(cfg, lr=0.5))


def tiny(name):
    return config(os.path.join(ROOT, "chipbench", "configs", name + ".json"))


def trains_only():
    """``tiny`` on a module made for this test: it has the three training
    names and no ``served_gaps``."""
    return dict(tiny("tiny"), name="trains-only", reference=os.path.join(
        "tests", "chipbench_tests", "reference_trains_only.py"))


@pytest.mark.parametrize("cfg,halves", [
    (tiny("tiny-gpt"), ["serving"]), (tiny("tiny"), ["training", "serving"]),
    (trains_only(), ["training"])],
    ids=["serves_only", "both", "trains_only"])
def test_the_contract_s_three_shapes(cfg, halves):
    assert held_to_what_it_exports(cfg) == halves


def test_a_module_with_neither_half_is_refused(monkeypatch):
    cfg = trains_only()
    monkeypatch.delattr(modules.reference_of(cfg), "train_reference")
    with pytest.raises(modules.ContractError,
                       match="neither half .* lacks served_gaps, .* lacks "
                             "train_reference$"):
        modules.reference_of(cfg)


@pytest.mark.parametrize("cfg,kind,lacks", [
    (tiny("tiny-gpt"), "train", "the training half (TRAINING)"),
    (trains_only(), "serve", "exports no served_gaps: the serving half "
                             "(SERVING)")],
    ids=["train_cell_on_serves_only", "serve_cell_on_trains_only"])
def test_a_cell_on_a_module_that_lacks_its_half(monkeypatch, cfg, kind,
                                                lacks):
    """``model_file.run`` asks by the cell's kind, before it builds
    anything, and stops with one plain line."""
    from chipbench import model_file
    spec = {"workload": {"name": "x", "kind": kind, "chips": 1},
            "config": cfg, "platform": "cpu", "t_start": 0.0, "seed": 1}
    monkeypatch.setenv(model_file.SPEC_ENV, json.dumps(spec))
    with pytest.raises(SystemExit) as stop:
        model_file.run(None, None)
    text = str(stop.value)
    assert text.startswith("chipbench: ") and "\n" not in text
    assert lacks in text


def test_the_parent_imports_no_jax():
    code = ("import sys; sys.path.insert(0, %r); import chipbench.run; "
            "assert 'jax' not in sys.modules" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


@pytest.mark.parametrize("cfg,message", [
    ({"name": "x", "reference": "chipbench/reference.py"}, "names no 'counts'"),
    ({"name": "x", "counts": "chipbench/no_such.py"}, "no file"),
    ({"name": "x", "counts": "../elsewhere.py"}, "no Python file of this"),
    ({"name": "x", "counts": "chipbench/peaks.json"}, "no Python file"),
    ({"name": "x", "counts": "chipbench/check.py"}, "exports no dims"),
], ids=["no_key", "no_file", "outside", "not_python", "lacks_names"])
def test_what_a_configuration_may_not_name(cfg, message):
    with pytest.raises(modules.ContractError, match=message):
        modules.counts_of(cfg)


def test_a_package_file_is_the_package_s_module():
    from chipbench import counts_dense
    assert modules.load_file("chipbench/counts_dense.py") is counts_dense
    assert modules.load_file(os.path.join(
        ROOT, "chipbench", "counts_dense.py")) is counts_dense


@pytest.mark.parametrize("path", HARNESS)
def test_harness_file_names_no_architecture(path):
    with open(os.path.join(ROOT, path)) as f:
        text = f.read()
    found = [name for name in ONE_ARCHITECTURE_S if name in text]
    assert not found, "%s names %s" % (path, found)

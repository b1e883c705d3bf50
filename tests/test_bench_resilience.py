"""bench.py and chip_smoke.py never put a host number under a chip
metric's name: each is one honest attempt at the chip — no chip (this
harness) or a failed section is a non-zero exit with NO metric line.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import_bench():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    return bench


def _run(script, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(REPO, script)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=REPO)


def _metric_lines(stdout):
    found = []
    for line in stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and ("metric" in doc or "ok" in doc):
            found.append(doc)
    return found


def test_bench_without_a_chip_exits_nonzero_and_prints_no_metric():
    r = _run("bench.py")
    assert r.returncode != 0
    assert _metric_lines(r.stdout) == []
    assert "samples_per_sec" not in r.stdout
    assert "'tpu'" in r.stderr and "cpu" in r.stderr   # names what's missing


def test_chip_smoke_without_a_chip_runs_no_phase():
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    assert _metric_lines(r.stdout) == []
    assert "no tpu device" in r.stderr and "cpu" in r.stderr
    for phase in ("train_1chip", "serve_1chip"):
        assert phase not in r.stdout + r.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode != 0 and r.stdout == ""
    assert "checkout" in r.stderr


def test_chip_smoke_verdict_line_has_the_contract_keys_only(
        tmp_path, monkeypatch, capsys):
    """The driver reads the LAST stdout line and refuses any key beyond
    ok/device{platform, kind, count}; the rest rides the summary line."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    phase = {"compile_seconds": 1.0, "wall_seconds": 2.0,
             "cache": str(tmp_path), "straddle_plane": "window",
             "tokens": {}}
    monkeypatch.setattr(chip_smoke, "LOG_DIR", str(tmp_path))
    monkeypatch.setattr(chip_smoke.signal, "signal", lambda *a: None)
    monkeypatch.setattr(chip_smoke, "probe_device", lambda: {
        "jax": "0.9.0", "platform": "tpu", "kind": "TPU v5 lite",
        "count": 1})
    monkeypatch.setattr(chip_smoke, "train_phase", lambda *a: dict(phase))
    monkeypatch.setattr(chip_smoke, "serve_phase", lambda *a: dict(phase))
    assert chip_smoke.main() == 0
    summary, verdict = capsys.readouterr().out.splitlines()[-2:]
    assert json.loads(verdict) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert summary.startswith("SMOKE_SUMMARY ")
    assert summary.endswith('"claim": null}')


class _FakeChip:
    platform = "tpu"
    device_count = 1


def test_a_failing_section_exits_nonzero(monkeypatch, capsys):
    """No section's exception is caught into an {"error": ...} extra:
    the process fails and the metric line is never printed."""
    bench = _import_bench()
    import veles_tpu as vt
    monkeypatch.setattr(vt, "Device_for", lambda name: _FakeChip())
    monkeypatch.setattr(bench, "bench_mnist", lambda dev, n: {
        "samples_per_sec_per_chip": 1.0, "max_window": 1.0,
        "epochs_per_dispatch": 8, "data": "synthetic"})

    def boom(dev, n):
        raise RuntimeError("conv-AE section blew up")

    monkeypatch.setattr(bench, "bench_conv_ae", boom)
    monkeypatch.setattr(bench, "bench_lm", lambda dev, n: {})
    with pytest.raises(RuntimeError, match="blew up"):
        bench.main()
    assert _metric_lines(capsys.readouterr().out) == []


def test_method_tag_encodes_dispatch_config(tmp_path, monkeypatch):
    """ADVICE r2: epochs_per_dispatch is methodology — a plan-mode
    baseline must never be compared against a block-dispatch run."""
    bench = _import_bench()
    monkeypatch.chdir(tmp_path)

    def fake_mnist(h):
        return {"samples_per_sec_per_chip": 100.0, "max_window": 110.0,
                "epochs_per_dispatch": h, "data": "synthetic"}

    # a LEGACY single-slot baseline (plan-mode 1.52M) must stay the
    # h=1 anchor, not be discarded or matched against h=8
    path = tmp_path / "BENCH_BASELINE.json"
    path.write_text(json.dumps({"value": 50.0,
                                "method": "median_of_3x10s",
                                "ts": 0}))
    monkeypatch.setattr(bench, "BASELINE_PATH", str(path))
    doc = bench._assemble(fake_mnist(8), {}, {}, "tpu", "kind",
                          allow_rebaseline=True)
    assert doc["window"] == "median_of_3x10s_h8"
    assert doc["rebaselined"] is True        # h8 had no anchor yet
    stored = json.load(open(path))
    # per-method slots: the h8 anchor lands WITHOUT evicting the
    # migrated legacy h=1 anchor
    assert stored["baselines"]["median_of_3x10s_h8"]["value"] == 100.0
    assert stored["baselines"]["median_of_3x10s"]["value"] == 50.0
    # a plan-mode run now compares against its own surviving anchor
    doc2 = bench._assemble(fake_mnist(1), {}, {}, "tpu", "kind",
                           allow_rebaseline=True)
    assert doc2["window"] == "median_of_3x10s"
    assert doc2["rebaselined"] is False
    assert doc2["vs_baseline"] == 2.0        # 100 vs the 50 anchor
    # and a repeat h8 run compares instead of flip-flop rebaselining
    doc3 = bench._assemble(fake_mnist(8), {}, {}, "tpu", "kind",
                           allow_rebaseline=True)
    assert doc3["rebaselined"] is False
    assert doc3["vs_baseline"] == 1.0

"""chip_smoke.py never puts a host number under a chip metric's name:
it is one honest attempt at the chip — no chip (this harness) is a
non-zero exit with NO metric line.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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

"""Test harness configuration.

Mirrors the reference's test strategy (SURVEY.md §4): tests run on a cheap,
always-available backend. Here that is the XLA CPU backend with 8 virtual
devices, so every sharding/collective test exercises a real 8-device mesh
without TPU hardware (the reference used in-process loopback ZeroMQ for the
same purpose, veles/tests/test_network.py).

Must run before jax is imported anywhere.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("VELES_TPU_TEST", "1")

# pin the config too: a pytest plugin may have imported jax before this
# file set the variable — this must happen before any backend is
# initialized
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running e2e tests, excluded from tier-1 via "
        "-m 'not slow'")


def import_model(name):
    """Import models/<name>.py as a module (models/ is not a package —
    mirrors the reference's import_file machinery, veles/import_file.py).
    Shared by model-zoo CI and feature tests."""
    import importlib.util
    import sys as _sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "models", name + ".py")
    spec = importlib.util.spec_from_file_location("models_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    _sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod

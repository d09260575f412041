"""Test env: put JAX on a virtual 8-device CPU platform, through the
environment and before any jax import, so sharding/collective tests run
without TPU hardware (chip_smoke.py is what runs on the real chip)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# the persistent compile cache (arroyo_tpu/ops/__init__.py) is for the chip:
# the CPU backend's entries save the tests nothing and every load of one
# logs machine-feature complaints. Worker subprocesses inherit this.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _storage(tmp_path, monkeypatch):
    """Point checkpoint storage at a fresh tmp dir for every test."""
    from arroyo_tpu import config as cfg
    from arroyo_tpu import faults
    from arroyo_tpu.state import storage as _st

    cfg.reset()
    cfg.update({
        "checkpoint.storage-url": str(tmp_path / "checkpoints"),
        # small device tables keep CPU-mode jit compile/exec fast in tests
        "device.table-capacity": 8192,
        "device.batch-capacity": 1024,
        "device.emit-capacity": 1024,
        "device.max-probes": 32,
        # chaos runs use sub-second retry delays; production default is 50ms
        "storage.retry.base-delay-ms": 10,
    })
    yield str(tmp_path / "checkpoints")
    # fault plans and storage circuit state never leak across tests
    faults.clear()
    _st.reset_retry_state()
    cfg.reset()


@pytest.fixture(scope="session", autouse=True)
def _operators():
    import arroyo_tpu

    arroyo_tpu._load_operators()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "no_native_required: runs even when the native library is unavailable")
    config.addinivalue_line(
        "markers", "chaos: fault-injection suite (runs pipelines under induced "
                   "failures and asserts byte-exact recovery)")
    config.addinivalue_line(
        "markers", "slow: long soak tests excluded from the tier-1 budget")
    config.addinivalue_line(
        "markers", "mesh: multi-device mesh execution suite (8 emulated "
                   "devices; tools/lint.sh --mesh-tests runs just these)")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On any failure while a fault plan is active, print the plan + seed
    (and which faults fired) so the chaos run can be replayed exactly."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when == "call" and rep.failed:
        try:
            from arroyo_tpu import faults

            inj = faults.active()
            if inj is not None:
                fired = "\n".join(inj.fired_log[-20:]) or "(no faults fired)"
                rep.sections.append((
                    "fault injection",
                    f"plan={inj.plan!r} seed={inj.seed}\nfired:\n{fired}",
                ))
        except Exception:
            pass

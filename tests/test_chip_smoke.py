"""chip_smoke.py's plumbing, on the CPU: the rehearsal drives every phase's
SQL job against its oracle at a few thousand events; without the rehearsal
argument a machine with no TPU is a non-zero exit that names the platform.
Plus the rules the chip run stands on: a planning/controller parent that
never initialises a jax backend (one process per chip), and where the
persistent compile cache goes.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # chip_smoke.py sits at the root of the checkout


def _run(args, cwd=REPO, env=None, timeout=300):
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, cwd=cwd, timeout=timeout,
                          env={**os.environ, **(env or {})})


def test_rehearsal_drives_every_phase_against_its_oracle(tmp_path, monkeypatch):
    import jax

    import chip_smoke

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    report = chip_smoke.run(rehearse=True, seed=3)
    failed = [(r["name"], r.get("error")) for r in report["phases"] if not r["ok"]]
    assert report["ok"] and not failed, failed
    labels = [r["phase"] for r in report["phases"]]
    # conftest gives 8 emulated devices, so the mesh phase rehearses too
    assert len(jax.devices()) >= 4
    assert labels == ["A"] + ["B"] * 5 + ["C"] * 2
    a = report["phases"][0]
    assert a["rows_compared"] > 0 and a["windows"] == 6
    assert 0 < a["leg2_source_rows"] < a["events"]  # restored mid-stream
    assert a["host_spill_batches"] == 0 and a["step_programs"] > 0
    by_name = {r["name"]: r for r in report["phases"]}
    assert by_name["windowed join, device probe"]["probe_programs"] > 0
    assert by_name["compiled segment"]["segment_fallback"] == 0
    assert by_name["mesh x4, fused segment"]["dispatch"]["fused_steps"] > 0
    assert report["preflight"]["rehearsal"] is True
    with open(tmp_path / "out" / "report.json") as f:
        assert json.load(f)["ok"] is True


def test_scoped_config_restores_exactly_what_was_there():
    """The smoke lays per-job overrides over the live config; a key the
    block introduced must be gone afterwards, not left behind as None (an
    undeclared key read with a default would then read None)."""
    from arroyo_tpu import config as cfg

    cfg.update({"device.table-capacity": 4096})
    with cfg.scoped({"device.table-capacity": 1024, "device.mesh-devices": 4,
                     "smoke.undeclared": 1}):
        assert cfg.config().get("device.table-capacity") == 1024
        assert cfg.config().get("device.mesh-devices") == 4
        assert cfg.config().get("smoke.undeclared") == 1
    assert cfg.config().get("device.table-capacity") == 4096
    assert cfg.config().get("device.mesh-devices") == 0  # its declared default
    assert cfg.config().get("smoke.undeclared", "unset") == "unset"


def test_without_a_tpu_it_exits_nonzero_and_names_the_platform():
    r = _run(["chip_smoke.py"], env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode not in (0, None)
    assert "platform='cpu'" in r.stderr
    assert '"ok"' not in r.stdout  # no summary, nothing to misread


def test_outside_a_checkout_it_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py"], cwd=str(tmp_path))
    assert r.returncode not in (0, None)
    assert r.stdout.strip() == ""


def test_run_parent_initialises_no_jax_backend(tmp_path):
    """`python -m arroyo_tpu run --scheduler process`: planning, the API,
    the controller and the scheduler live in the parent; the engine lives
    in worker children. A parent that touched the backend would hold the
    chip its own worker needs."""
    out = tmp_path / "out.json"
    sql = tmp_path / "q.sql"
    sql.write_text(f'''
CREATE TABLE nexmark ("bid" BOOLEAN, "bid.auction" BIGINT, "bid.price" BIGINT)
WITH (connector = 'nexmark', event_count = '20000', inter_event_micros = 1000,
      first_event_micros = 0);
CREATE TABLE out (auction BIGINT, mx BIGINT, ws TIMESTAMP)
WITH (connector = 'single_file', path = '{out}', format = 'json', type = 'sink');
INSERT INTO out SELECT auction, mx, window.start FROM (
  SELECT "bid.auction" AS auction, max("bid.price") AS mx,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window);
''')
    code = (
        "import sys\n"
        "from arroyo_tpu import cli\n"
        f"rc = cli.main(['run', {str(sql)!r}, '--scheduler', 'process', '--timeout', '240'])\n"
        "from jax._src import xla_bridge\n"
        "print('RC', rc, 'BACKENDS', sorted(xla_bridge._backends))\n"
    )
    r = _run(["-c", code], env={
        "ARROYO_TPU__CHECKPOINT__STORAGE_URL": str(tmp_path / "ckpt")})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "RC 0 BACKENDS []" in r.stdout, (r.stdout, r.stderr[-3000:])
    assert out.read_text().count("\n") > 0  # the child did run the job


def test_compile_cache_dir_from_env_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the code sets no other directory, and
    entries appear there."""
    cache = tmp_path / "cache"
    code = (
        "import jax, arroyo_tpu.ops\n"
        "import jax.numpy as jnp\n"
        "print('DIR', jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n"
    )
    r = _run(["-c", code], env={
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        "JAX_ENABLE_COMPILATION_CACHE": "true"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert f"DIR {cache}" in r.stdout
    assert os.listdir(cache), "no cache entry was written where the env said"


def test_compile_cache_dir_defaults_to_one_fixed_path_in_the_checkout():
    """Unset: a fixed directory inside the checkout — the same from every
    process, so a later run (or a worker child) finds what this one
    compiled. Never a temp name."""
    code = ("import jax, arroyo_tpu.ops\n"
            "print('DIR', jax.config.jax_compilation_cache_dir)\n")
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    seen = set()
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, cwd=REPO, timeout=120, env=env)
        assert r.returncode == 0, r.stderr[-3000:]
        seen.add(r.stdout.strip())
    assert seen == {f"DIR {os.path.join(REPO, '.jax_cache')}"}

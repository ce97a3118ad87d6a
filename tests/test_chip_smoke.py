"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script refuses any device but a TPU, so these tests patch its device
check (and its compile-cache call) inside the test and shrink its table
sizes; everything else — build, warmup, serve through ``ServingEngine``,
the host exact answers, the update read-backs, the sharded comparison and
the last JSON line — runs as on the chip.
"""
import importlib
import json
import os
import shutil
import subprocess
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _smoke():
    sys.path.insert(0, ROOT)
    try:
        return importlib.import_module("chip_smoke")
    finally:
        sys.path.remove(ROOT)


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_refuses_a_host_without_tpu():
    r = _run([SCRIPT], ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_refuses_to_run_without_the_repository(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run([str(tmp_path / "chip_smoke.py")], str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_single_chip_path_end_to_end(monkeypatch, capsys):
    smoke = _smoke()
    import repro.compile_cache as cc
    monkeypatch.setattr(smoke, "require_tpu", lambda count=1: jax.devices())
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off (test)")
    monkeypatch.setattr(smoke, "N1", 6_000)
    monkeypatch.setattr(smoke, "N2", 2_000)
    monkeypatch.setattr(smoke, "BATCHES", 1)
    assert smoke.main([]) == 0
    out = capsys.readouterr().out
    lines = out.strip().splitlines()
    for kind in smoke.KINDS:
        assert any(ln.startswith(f"serve: {kind} ") for ln in lines), kind
    assert "compiles_in_phase=0" in out
    assert "precompile_failures=0" in out
    last = _last_json(out)
    assert last == {"ok": True, "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}}


def test_four_chip_path_on_four_host_devices():
    code = (
        "import jax, chip_smoke as s, repro.compile_cache as cc\n"
        "s.require_tpu = lambda count=1: jax.devices()\n"
        "cc.enable_compile_cache = lambda: 'off (test)'\n"
        "s.N1_SHARDED, s.N2_SHARDED = 6000, 2000\n"
        "raise SystemExit(s.main(['--chips', '4']))\n")
    r = _run(["-c", code], ROOT, {
        "PYTHONPATH": ROOT,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    sharded = [ln for ln in lines if ln.startswith("sharded: ")]
    assert len(sharded) == 8
    # on one host's CPU devices the sharded executors repeat the unsharded
    # arithmetic exactly (engine/sharded.py)
    assert all("bit_identical=True" in ln for ln in sharded), sharded
    assert not any(ln.startswith(("serve: ", "update: ")) for ln in lines)
    assert _last_json(r.stdout)["device"]["count"] == 4

"""``chip_smoke.py`` on the CPU: its phases at tiny sizes in interpret mode,
its refusal to run (or print a result) without a TPU, and where its compile
cache goes."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

H, W = 40, 64


def test_single_chip_phases_tiny():
    """(a) -> (b) -> (c) at a tiny frame size: each phase passes its own
    checks through plan_for -> BGPlan -> engine / packer, with no fallback,
    retry or failure."""
    res, frames, outs = chip_smoke.phase_paper(h=H, w=W, n_frames=3)
    assert res["ok"], res
    assert res["max_abs_diff"] <= 1.0 and res["engine"]["dispatches"] == 1
    res_b = chip_smoke.phase_bf16(frames, outs)
    assert res_b["ok"], res_b
    assert "prec=bf16" in res_b["plan"]
    res_c = chip_smoke.phase_temporal(h=H, w=W, n_streams=2, n_frames=3)
    assert res_c["ok"], res_c
    assert "temporal=1" in res_c["plan"] and res_c["carry_within_tolerance"]


def test_four_chip_phase_on_forced_host_mesh():
    code = textwrap.dedent(
        f"""
        import os, sys, json
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        res = chip_smoke.phase_four_chips(h={H}, w={W}, n_frames=6)
        print('RESULT ' + json.dumps(res))
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420, env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("RESULT "))
    res = json.loads(line[len("RESULT "):])
    assert res["ok"] and res["bitwise_equal"], res
    assert "mesh=4" in res["plan"]


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_no_result_without_a_tpu(tmp_path, alone):
    """No TPU (and, alone, no repo beside it): non-zero exit, no result
    line on stdout."""
    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=300, env=env, cwd=tmp_path,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_dir_follows_env_then_checkout(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins; otherwise <checkout>/.jax_cache."""
    import jax

    from repro.compile_cache import CACHE_ENV_VAR, enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(CACHE_ENV_VAR, str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv(CACHE_ENV_VAR)
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

"""``chip_smoke.py`` between chip runs (ISSUE 21).

The script is the repo's proof that the main path runs on a TPU, and it
only ever runs there — so tier-1 drives every leg of it at a tiny size
on the CPU, with the chip requirement explicitly waived, to keep it from
rotting. Beside it: the one decision about Pallas interpret mode
(``disq_tpu.util.pallas_interpret``) raises on a backend that is neither
a TPU nor an explicitly requested CPU, and the compile-cache function
leaves ``JAX_COMPILATION_CACHE_DIR`` alone.
"""

import json
import os
import shutil
import subprocess
import sys
import types
import zlib

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
LEGS = ("host_read", "host_sort_write", "device_read", "device_sort_write",
        "operators", "cram", "serve", "mesh")


TINY = ["--allow-cpu", "--records", "60", "--block-payload", "300",
        "--cram-records", "40", "--split-size", "5000"]


def _run(args, cwd=REPO, script=SMOKE, **env):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True,
        text=True, timeout=600, env={**os.environ, **env})


def _check_summary(proc, cache, legs):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    # the last line is the verdict: exactly these keys, nothing after it
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["count"], int)
    # the line before it is the run's summary
    doc = json.loads(lines[-2])
    assert doc["ok"] is True and doc["claim"] is None
    assert doc["device"] == verdict["device"]
    assert list(doc)[-1] == "claim"
    # a waived run says what it was: CPU, interpreter, not full size
    assert doc["device"]["platform"] == "cpu" and doc["interpret"] is True
    assert doc["full_size"] is False
    for leg in legs:
        assert doc["legs"][leg]["cold_s"] > 0, leg
        assert doc["legs"][leg]["second_s"] > 0, leg
    launches = doc["counters"]["kernel_launches"]
    for kernel in ("inflate_simd", "columnar_parse", "rans_simd"):
        assert launches[f"kernel={kernel}"] > 0, kernel
    assert doc["counters"]["host_fallback_blocks"].get(
        "reason=flagged", 0) == 0
    assert doc["blocks"]["device_served"] > 0
    # JAX_COMPILATION_CACHE_DIR was set: that directory, and no other
    assert doc["compile_cache"]["dir"] == str(cache)
    assert doc["compile_cache"]["entries"] == len(os.listdir(cache)) > 0
    return doc


def test_every_one_chip_leg_runs_tiny_on_cpu_with_the_chip_waived(tmp_path):
    """One CPU device: the mesh leg reports its skip, as it does on a
    one-chip machine (the slow tier and the four-chip host run it)."""
    cache = tmp_path / "cache"
    proc = _run(TINY, JAX_COMPILATION_CACHE_DIR=str(cache), XLA_FLAGS="")
    doc = _check_summary(proc, cache, LEGS[:-1])
    assert doc["legs"]["mesh"] == {"skipped": "1 device"}
    # demanding four chips turns the skip into a failure, with no result
    proc = _run(TINY + ["--chips", "4"], XLA_FLAGS="")
    assert proc.returncode != 0 and '"ok"' not in proc.stdout


@pytest.mark.slow
def test_mesh_leg_runs_tiny_on_four_virtual_devices(tmp_path):
    cache = tmp_path / "cache"
    proc = _run(TINY + ["--chips", "4"],
                JAX_COMPILATION_CACHE_DIR=str(cache))
    doc = _check_summary(proc, cache, LEGS)
    assert doc["counters"]["kernel_launches"][
        "kernel=mesh_sort_exchange"] > 0
    assert len(doc["notes"]["mesh"]["lane_fill_rows"]) == 4


def test_refuses_to_run_on_cpu_without_the_waiver():
    proc = _run(["--records", "60"], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_fails_in_a_directory_that_holds_nothing_else(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(alone), "--allow-cpu"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


class TestPallasInterpret:
    """One helper decides interpret mode for every kernel entry point."""

    @staticmethod
    def _fake_jax(monkeypatch, backend, platforms):
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(
            jax, "config", types.SimpleNamespace(jax_platforms=platforms))

    def test_tpu_compiles_and_explicit_cpu_interprets(self, monkeypatch):
        from disq_tpu.util import pallas_interpret

        self._fake_jax(monkeypatch, "tpu", None)
        assert pallas_interpret() is False
        self._fake_jax(monkeypatch, "cpu", "cpu")
        assert pallas_interpret() is True

    @pytest.mark.parametrize("backend,platforms", [
        ("cpu", None),        # jax fell back to the CPU on its own
        ("cpu", "tpu,cpu"),   # the chip was asked for and did not attach
        ("gpu", None),
    ])
    def test_armed_knob_on_any_other_backend_raises(
            self, monkeypatch, backend, platforms):
        from disq_tpu.ops.inflate_simd import inflate_payloads_simd
        from disq_tpu.ops.rans_simd import rans0_decode_simd
        from disq_tpu.cram.rans import rans_encode_order0

        self._fake_jax(monkeypatch, backend, platforms)
        raw = b"chip or nothing " * 8
        c = zlib.compressobj(6, zlib.DEFLATED, -15, 8)
        payload = c.compress(raw) + c.flush()
        with pytest.raises(RuntimeError, match="need a TPU backend"):
            inflate_payloads_simd([payload], usizes=[len(raw)])
        with pytest.raises(RuntimeError, match="need a TPU backend"):
            rans0_decode_simd([rans_encode_order0(raw)])


class TestCompileCache:
    def test_env_dir_is_left_alone(self, monkeypatch, tmp_path):
        import jax

        from disq_tpu.util import enable_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_points_inside_the_checkout(self, monkeypatch):
        import jax

        from disq_tpu.util import enable_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            path = enable_compile_cache()
            assert path == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            # fixed: the path is part of what a later run must find
            assert enable_compile_cache() == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

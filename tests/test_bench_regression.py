"""Tier-1 guard for ``scripts/check_bench_regression.py``: the
trajectory comparator must pass the repo's real BENCH_r*.json history,
fail a synthetic regressed round, and honor each config's measured
spread — all from fixture JSONs, never by invoking bench.py."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "check_bench_regression.py")


@pytest.fixture(scope="module")
def cbr():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import check_bench_regression as mod
    finally:
        sys.path.pop(0)
    return mod


def _round(tmp_path, n, *, primary=100_000.0, spread=0.02, configs=None):
    """Write one harness-shaped BENCH_rNN.json fixture."""
    doc = {"n": n, "rc": 0, "parsed": {
        "metric": "bam_decode_records_per_sec", "value": primary,
        "unit": "records/sec", "spread": spread,
        "configs": configs or {},
    }}
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps(doc))
    return path


def test_repo_trajectory_passes():
    """Acceptance: the repo's own BENCH_r*.json trajectory is green."""
    proc = subprocess.run([sys.executable, SCRIPT],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK: no config dropped" in proc.stdout


def test_repo_list_prints_trajectory():
    proc = subprocess.run([sys.executable, SCRIPT, "--list"],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "primary.bam_decode_records_per_sec" in proc.stdout
    # every round of the history shows as a column
    for col in ("r01", "r06"):
        assert col in proc.stdout


def test_regressed_fixture_fails(cbr, tmp_path):
    """Acceptance: a synthetic 30% drop past the band exits nonzero
    and names the config."""
    cfg1 = {"6_scaling": {"workers_8": {"records_per_sec": 800_000.0,
                                        "spread": 0.02}}}
    cfg2 = {"6_scaling": {"workers_8": {"records_per_sec": 560_000.0,
                                        "spread": 0.02}}}
    _round(tmp_path, 1, configs=cfg1)
    _round(tmp_path, 2, configs=cfg2)
    rc = cbr.main(["--dir", str(tmp_path)])
    assert rc == 1

    proc = subprocess.run(
        [sys.executable, SCRIPT, "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "REGRESSION" in proc.stdout
    assert "6_scaling.workers_8.records_per_sec" in proc.stdout


def test_small_drop_within_band_passes(cbr, tmp_path):
    _round(tmp_path, 1, primary=100_000.0)
    _round(tmp_path, 2, primary=92_000.0)  # -8% < 15% band
    assert cbr.main(["--dir", str(tmp_path)]) == 0


def test_spread_widens_the_band(cbr, tmp_path):
    """A 25% drop fails a tight config but passes one whose own
    measured spread is 0.2 — the band honors per-config noise."""
    noisy1 = {"x": {"records_per_sec": 100_000.0, "spread": 0.2}}
    noisy2 = {"x": {"records_per_sec": 75_000.0, "spread": 0.2}}
    _round(tmp_path, 1, configs=noisy1)
    _round(tmp_path, 2, configs=noisy2)
    assert cbr.main(["--dir", str(tmp_path)]) == 0  # 25% < 15% + 20%

    tight = tmp_path / "tight"
    tight.mkdir()
    tight1 = {"x": {"records_per_sec": 100_000.0, "spread": 0.01}}
    tight2 = {"x": {"records_per_sec": 75_000.0, "spread": 0.01}}
    _round(tight, 1, configs=tight1)
    _round(tight, 2, configs=tight2)
    assert cbr.main(["--dir", str(tight)]) == 1  # 25% > 15% + 1%


def test_staged_rows_use_their_own_spread_key(cbr, tmp_path):
    """bench config 8 carries staged_records_per_sec/staged_spread —
    the extractor must pair them, not borrow the local row's spread."""
    cfg = {"8_write": {"workers_4": {
        "records_per_sec": 200_000.0, "spread": 0.01,
        "staged_records_per_sec": 90_000.0, "staged_spread": 0.3,
    }}}
    series = cbr.extract_series(cfg)
    assert series["8_write.workers_4.records_per_sec"] == (200_000.0, 0.01)
    assert series["8_write.workers_4.staged_records_per_sec"] == (
        90_000.0, 0.3)


def _serve_cfg(p99, spread=0.02, qps=2000.0):
    """Config-13-shaped row: hot latency percentiles + QPS at c=32."""
    return {"13_serve_latency": {"clients_32": {
        "cold_p99_ms": 500.0,
        "hot": {"p50_ms": p99 / 4, "p99_ms": p99, "p999_ms": p99 * 1.5,
                "spread": spread, "qps": qps, "qps_spread": 0.03},
    }}}


def test_serve_latency_is_lower_is_better(cbr, tmp_path):
    """Satellite: a +30% hot p99 at c=32 must FAIL even though every
    other guarded series is higher-is-better."""
    _round(tmp_path, 1, configs=_serve_cfg(40.0))
    _round(tmp_path, 2, configs=_serve_cfg(52.0))  # +30% > 25% + 2%
    rc = cbr.main(["--dir", str(tmp_path)])
    assert rc == 1

    proc = subprocess.run(
        [sys.executable, SCRIPT, "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "13_serve_latency.clients_32.hot.p99_ms" in proc.stdout


def test_serve_latency_improvement_passes(cbr, tmp_path):
    """A lower p99 is an improvement, never a 'drop'."""
    _round(tmp_path, 1, configs=_serve_cfg(40.0))
    _round(tmp_path, 2, configs=_serve_cfg(8.0))  # 5x better
    assert cbr.main(["--dir", str(tmp_path)]) == 0


def test_serve_latency_within_band_passes(cbr, tmp_path):
    _round(tmp_path, 1, configs=_serve_cfg(40.0))
    _round(tmp_path, 2, configs=_serve_cfg(44.0))  # +10% < 25% band
    assert cbr.main(["--dir", str(tmp_path)]) == 0


def test_serve_qps_drop_fails_higher_is_better(cbr, tmp_path):
    """The same config's QPS row keeps the higher-is-better sense."""
    _round(tmp_path, 1, configs=_serve_cfg(40.0, qps=2000.0))
    _round(tmp_path, 2, configs=_serve_cfg(40.0, qps=1000.0))
    rc = cbr.main(["--dir", str(tmp_path)])
    assert rc == 1


def test_cold_percentiles_are_not_guarded(cbr, tmp_path):
    """Cold numbers are context (first-touch, dominated by one-off
    I/O), not a guarded series — only leaf ``p99_ms`` keys are."""
    series = cbr.extract_series(_serve_cfg(40.0))
    assert "13_serve_latency.clients_32.hot.p99_ms" in series
    assert series["13_serve_latency.clients_32.hot.p99_ms"] == (40.0, 0.02)
    assert not any("cold" in k for k in series)


def _calib_cfg(fw, base, *, spread=0.02, base_spread=0.01, extra=None):
    """Config-1-shaped round: framework value + the stdlib host ruler."""
    cfgs = {"1_bam_decode": {"records_per_sec": fw, "spread": spread,
                             "baseline_records_per_sec": base,
                             "baseline_spread": base_spread}}
    if extra:
        cfgs.update(extra)
    return cfgs


def test_host_drift_normalizes_a_uniform_slowdown(cbr, tmp_path):
    """Satellite: a round on a 0.6x container — ruler AND framework
    both ~40% down — must pass: the drop is the machine, not the
    code. Raw comparison would fail at -40% vs a 17% band."""
    _round(tmp_path, 1, primary=2_000_000.0,
           configs=_calib_cfg(2_000_000.0, 500_000.0))
    _round(tmp_path, 2, primary=1_200_000.0,
           configs=_calib_cfg(1_200_000.0, 300_000.0))
    assert cbr.main(["--dir", str(tmp_path)]) == 0

    proc = subprocess.run(
        [sys.executable, SCRIPT, "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "HOST DRIFT" in proc.stdout


def test_host_drift_does_not_mask_a_real_break(cbr, tmp_path):
    """Drift mode widens bands, it does not disable them: a 5x drop
    on a 0.6x container is still ~3x past any slack."""
    _round(tmp_path, 1, primary=2_000_000.0,
           configs=_calib_cfg(2_000_000.0, 500_000.0))
    _round(tmp_path, 2, primary=400_000.0,
           configs=_calib_cfg(400_000.0, 300_000.0))
    assert cbr.main(["--dir", str(tmp_path)]) == 1


def test_stable_host_keeps_tight_bands(cbr, tmp_path):
    """When the ruler holds still the full-precision band applies —
    a 30% framework drop fails even though both rounds carry rulers."""
    _round(tmp_path, 1, primary=2_000_000.0,
           configs=_calib_cfg(2_000_000.0, 500_000.0))
    _round(tmp_path, 2, primary=1_400_000.0,
           configs=_calib_cfg(1_400_000.0, 495_000.0))
    assert cbr.main(["--dir", str(tmp_path)]) == 1


def test_new_and_retired_configs_never_fail(cbr, tmp_path):
    _round(tmp_path, 1, configs={"old": {"records_per_sec": 1000.0}})
    _round(tmp_path, 2, configs={"new": {"records_per_sec": 5.0}})
    assert cbr.main(["--dir", str(tmp_path)]) == 0


def test_single_round_is_a_noop(cbr, tmp_path):
    _round(tmp_path, 1)
    assert cbr.main(["--dir", str(tmp_path)]) == 0

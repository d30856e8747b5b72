"""chip_smoke.py and what it leans on: the CPU refusal, the compile-cache
resolver, the idle-device warning, the dry run's device check — and (slow)
the smoke's phase functions at toy size on the CPU mesh."""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from dinunet_implementations_tpu.core import jaxcompat  # noqa: E402
from dinunet_implementations_tpu.core.config import TrainConfig  # noqa: E402


def test_chip_smoke_refuses_a_cpu():
    """`python chip_smoke.py` with no accelerator: non-zero exit, the
    platform it found named, no result object printed."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    assert "refusing to run" in proc.stdout and "'cpu'" in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.fixture
def cache_config():
    """Put jax's compile-cache settings back however a test left them."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_cache_resolver_environment_wins(monkeypatch, tmp_path, cache_config):
    """JAX_COMPILATION_CACHE_DIR set: it is the resolved directory and no
    code path sets another; the write thresholds are still lowered."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    env_dir, cfg_dir = str(tmp_path / "env"), str(tmp_path / "cfg")
    monkeypatch.setenv(jaxcompat.CACHE_ENV, env_dir)
    calls = []
    monkeypatch.setattr(cc, "set_cache_dir", calls.append)
    before = jax.config.jax_compilation_cache_dir
    assert jaxcompat.resolve_compile_cache_dir(cfg_dir) == env_dir
    assert jaxcompat.enable_compile_cache(cfg_dir) == env_dir
    assert jaxcompat.enable_compile_cache("") == env_dir
    assert calls == []
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(cfg_dir)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_resolver_config_applies_without_environment(
        monkeypatch, tmp_path, cache_config):
    monkeypatch.delenv(jaxcompat.CACHE_ENV, raising=False)
    cfg_dir = str(tmp_path / "cfg")
    assert jaxcompat.resolve_compile_cache_dir("") == ""
    assert jaxcompat.enable_compile_cache("") == ""
    assert jaxcompat.enable_compile_cache(cfg_dir) == cfg_dir
    assert jax.config.jax_compilation_cache_dir == cfg_dir
    assert os.path.isdir(cfg_dir)


def _fake_devices(n, platform="tpu"):
    return [
        types.SimpleNamespace(
            id=i, platform=platform, device_kind="TPU v5 lite",
            process_index=0,
        )
        for i in range(n)
    ]


def test_fold_onto_one_of_several_chips_warns(monkeypatch, capsys):
    """32 sites, 4 chips, default --sites-per-device 1: the resolver still
    folds onto the first device, but says which devices stay idle and which
    flag spreads the sites."""
    from dinunet_implementations_tpu.runner.fed_runner import auto_site_mesh

    monkeypatch.setattr(jax, "devices", lambda *a: _fake_devices(4))
    assert auto_site_mesh(TrainConfig(), 32) is None
    out = capsys.readouterr().out
    assert "32 sites folded onto one device" in out
    assert "[warn] 3 of 4 tpu devices stay idle" in out
    assert "--sites-per-device" in out and "id=3" in out
    # one chip: nothing idles, nothing to warn about
    monkeypatch.setattr(jax, "devices", lambda *a: _fake_devices(1))
    assert auto_site_mesh(TrainConfig(), 32) is None
    assert "[warn]" not in capsys.readouterr().out


def test_auto_site_mesh_logs_the_devices_it_uses(capsys):
    from dinunet_implementations_tpu.runner.fed_runner import auto_site_mesh

    mesh = auto_site_mesh(TrainConfig(sites_per_device=2), 8)
    assert dict(mesh.shape)["site"] == 4
    out = capsys.readouterr().out
    assert "[mesh] 8 sites on 4 of 8 cpu device(s)" in out
    assert str(jax.devices()[3]) in out and str(jax.devices()[4]) not in out


def test_dryrun_multichip_raises_without_the_devices():
    import __graft_entry__ as entry

    with pytest.raises(RuntimeError, match="needs 64 devices"):
        entry.dryrun_multichip(64)


@pytest.mark.slow
def test_smoke_phases_at_toy_size_on_cpu(monkeypatch, tmp_path, cache_config):
    """Every phase, mesh phase included (8 virtual devices), through the
    same functions `python chip_smoke.py` runs on the chip; a second run
    against the same compile cache adds no entries."""
    monkeypatch.delenv(jaxcompat.CACHE_ENV, raising=False)
    dims = chip_smoke.Dims(
        sites=8, subjects=24, comps=6, temporal=40, window=5, stride=5,
        input_size=16, hidden_size=12, batch=4, compute_dtype="bfloat16",
    )
    dirs = [str(tmp_path / d) for d in ("data", "out", "cache")]
    first = chip_smoke.run(dims, "cpu", *dirs)
    assert all(
        name in first["phases"] for name in (
            "kernel/parity", "train/dSGD", "train/rankDAD", "serve",
            "train/dSGD-mesh",
        )
    )
    assert first["phases"]["train/dSGD-mesh"]["mesh"]["site"] == 4
    assert first["cache_entries_before"] == 0 < first["cache_entries_after"]
    assert json.load(open(os.path.join(dirs[1], "report.json"))) == first
    second = chip_smoke.run(dims, "cpu", *dirs)
    assert second["cache_entries_after"] == first["cache_entries_after"]
    assert (second["phases"]["train/dSGD"]["losses"]
            == first["phases"]["train/dSGD"]["losses"])

"""chip_smoke.py off the chip: the same phases at a tiny size on the virtual
CPU mesh with kernels interpreted, the script's refusal to run without a
TPU, and where the compile cache goes."""

import os
import subprocess
import sys
import time

import pytest

jax = pytest.importorskip("jax")

import chip_smoke
from pslite_tpu.parallel.mesh import default_mesh
from pslite_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.Sizes(
    dense_buckets=(("t0", 1000), ("t1", 4100), ("t2", 1000)),
    steps=2,
    readme_keys=4, readme_val_len=64,
    emb_rows=4096, emb_dim=8, emb_batch=64, emb_odd_rows=4099,
)


def _muon_alone(monkeypatch, devices: int) -> None:
    """Boot, the ``muon`` phase on an engine over the first ``devices`` of
    the eight, shutdown."""
    import numpy as np
    from jax.sharding import Mesh

    from pslite_tpu.parallel.engine import CollectiveEngine

    mesh = Mesh(np.array(jax.devices()[:devices]), ("kv",))

    def phase(self):
        self.kv.po.van.engine = CollectiveEngine(
            mesh=mesh, server_handle=chip_smoke.SERVER_HANDLE)
        self.muon()

    monkeypatch.setattr(
        chip_smoke._Smoke, "phases",
        lambda self: [("boot", 60, self.boot),
                      ("muon", 150, lambda: phase(self)),
                      ("shutdown", 30, self.shutdown)])
    chip_smoke.run_smoke(default_mesh(), TINY)


def test_smoke_phases_at_tiny_size(capsys):
    hung = []
    chip_smoke.run_smoke(default_mesh(), TINY,
                         expired=lambda name, s: hung.append(name))
    assert not hung
    out = capsys.readouterr().out
    for phase in ("boot", "resnet50", "readme", "lamb", "mixed", "muon",
                  "sparse", "message_path", "shutdown"):
        assert f"phase {phase}: ok" in out
    assert "W = 8, kernels interpreted" in out
    # PR 56: over the eight shards both trees lie by their owners, a matrix
    # each as far as the matrices go (seven, and four).
    assert "7 owners over 8 shards" in out and "4 owners over 8 shards" in out


def test_the_muon_phase_over_four_owners(capsys, monkeypatch):
    """PR 56: over four shards (the host the benchmark's four-chip cell
    runs on) both trees are sharded on their keys' borders and run their
    two steps against the float64 recurrence; the pulled tree is the
    gather laid back into key order, no kernel's own vector."""
    _muon_alone(monkeypatch, 4)
    out = capsys.readouterr().out
    assert "phase muon: ok" in out
    assert out.count(
        "2 steps under muon:0.001,0.95,0.1,0.9,0.95,1e-08 agree") == 2
    assert out.count("pulled by the kernels 0 of 2") == 2


def test_the_muon_phase_where_one_shard_holds_the_bucket(capsys, monkeypatch):
    """On one device the plan is the identity."""
    _muon_alone(monkeypatch, 1)
    out = capsys.readouterr().out
    assert "phase muon: ok" in out
    assert "2 steps under muon:0.001,0.95,0.1,0.9,0.95,1e-08 agree" in out
    # PR 45: the first bucket keeps the program's cut (a key of it is no
    # kernel's), the second pulls the kernels' own vector.
    assert ("muon_row_keys 3, muon_apply_keys 3, pulled by the kernels "
            "0 of 2") in out
    assert ("muon_row_keys 7, muon_apply_keys 7, pulled by the kernels "
            "2 of 2") in out


def test_failing_phase_is_named(monkeypatch):
    def boom():
        raise ValueError("no such bucket")

    monkeypatch.setattr(
        chip_smoke._Smoke, "phases",
        lambda self: [("first", 5, lambda: None), ("second", 5, boom)],
    )
    with pytest.raises(chip_smoke.PhaseFailed, match="second") as err:
        chip_smoke.run_smoke(default_mesh(), TINY)
    assert isinstance(err.value.__cause__, ValueError)


def test_deadline_names_the_phase_that_hangs():
    hung = []
    with chip_smoke.deadline("stuck", 0.05,
                             expired=lambda name, s: hung.append((name, s))):
        time.sleep(0.3)
    assert hung == [("stuck", 0.05)]
    with chip_smoke.deadline("quick", 5, expired=lambda *a: hung.append(a)):
        pass
    assert len(hung) == 1


def test_script_refuses_to_run_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert "platform: cpu" in out.stdout
    assert "phase" not in out.stdout  # before any work
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        # Placed from outside: no directory is set in code.
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(_REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path  # fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)

"""Compile-cache seeder (tools/seed_compile_cache.py): a warm node's
XLA cache exports as one generation-keyed bundle and a fresh node
booting from the imported seed pays zero live compiles for the seeded
signatures."""

import json
import os
import subprocess
import sys
import tarfile

import pytest

from elasticsearch_tpu.tools import seed_compile_cache as seed


def _fake_cache(tmp_path, name="warm", files=None):
    d = tmp_path / name
    d.mkdir()
    for rel, data in (files or {"jit_fn-sig0": b"xla-blob-0",
                                "jit_fn-sig1": b"xla-blob-1" * 100,
                                "sub/dir-entry": b"nested"}).items():
        p = d / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(data)
    return d


class TestBundleRoundTrip:
    def test_export_import_round_trips_artifacts(self, tmp_path):
        warm = _fake_cache(tmp_path)
        bundle = tmp_path / "seed.tar.gz"
        manifest = seed.export_bundle(str(warm), str(bundle),
                                      generation="cpu/1.0/1.0")
        assert manifest["generation"] == "cpu/1.0/1.0"
        assert [f["name"] for f in manifest["files"]] \
            == sorted(f["name"] for f in manifest["files"])
        cold = tmp_path / "cold"
        summary = seed.import_bundle(str(bundle), str(cold),
                                     generation="cpu/1.0/1.0")
        assert sorted(summary["imported"]) == sorted(
            f["name"] for f in manifest["files"])
        assert summary["skipped"] == []
        for f in manifest["files"]:
            src = (warm / f["name"]).read_bytes()
            assert (cold / f["name"]).read_bytes() == src

    def test_manifest_is_first_member(self, tmp_path):
        warm = _fake_cache(tmp_path)
        bundle = tmp_path / "seed.tar.gz"
        seed.export_bundle(str(warm), str(bundle), generation="g")
        with tarfile.open(bundle) as tar:
            assert tar.getmembers()[0].name == seed.MANIFEST_NAME

    def test_export_refuses_missing_or_empty_dir(self, tmp_path):
        with pytest.raises(SystemExit, match="does not exist"):
            seed.export_bundle(str(tmp_path / "nope"),
                               str(tmp_path / "out.tar.gz"))
        empty = tmp_path / "empty"
        empty.mkdir()
        with pytest.raises(SystemExit, match="no"):
            seed.export_bundle(str(empty), str(tmp_path / "out.tar.gz"))

    def test_import_skips_existing_live_artifacts(self, tmp_path):
        warm = _fake_cache(tmp_path)
        bundle = tmp_path / "seed.tar.gz"
        seed.export_bundle(str(warm), str(bundle), generation="g")
        cold = tmp_path / "cold"
        cold.mkdir()
        # a live cache entry must win over the seed's copy
        (cold / "jit_fn-sig0").write_bytes(b"live-entry-newer")
        summary = seed.import_bundle(str(bundle), str(cold),
                                     generation="g")
        assert summary["skipped"] == ["jit_fn-sig0"]
        assert (cold / "jit_fn-sig0").read_bytes() == b"live-entry-newer"

    def test_corrupt_bundle_fails_checksum_and_cleans_up(self, tmp_path):
        warm = _fake_cache(tmp_path, files={"entry": b"good"})
        bundle = tmp_path / "seed.tar.gz"
        seed.export_bundle(str(warm), str(bundle), generation="g")
        # rebuild the tar with the same manifest but tampered payload
        with tarfile.open(bundle) as tar:
            manifest_data = tar.extractfile(seed.MANIFEST_NAME).read()
        evil = tmp_path / "evil.tar.gz"
        import io
        with tarfile.open(evil, "w:gz") as tar:
            info = tarfile.TarInfo(seed.MANIFEST_NAME)
            info.size = len(manifest_data)
            tar.addfile(info, io.BytesIO(manifest_data))
            payload = b"EVIL"
            info = tarfile.TarInfo("entry")
            info.size = len(payload)
            tar.addfile(info, io.BytesIO(payload))
        cold = tmp_path / "cold"
        with pytest.raises(SystemExit, match="checksum mismatch"):
            seed.import_bundle(str(evil), str(cold), generation="g")
        assert not (cold / "entry").exists()


class TestGenerationKeying:
    def test_mismatch_refused_then_forced(self, tmp_path):
        warm = _fake_cache(tmp_path)
        bundle = tmp_path / "seed.tar.gz"
        seed.export_bundle(str(warm), str(bundle),
                           generation="tpu-v4/0.9/0.9")
        cold = tmp_path / "cold"
        with pytest.raises(SystemExit, match="does not match"):
            seed.import_bundle(str(bundle), str(cold),
                               generation="cpu/1.0/1.0")
        summary = seed.import_bundle(str(bundle), str(cold),
                                     generation="cpu/1.0/1.0",
                                     force=True)
        assert summary["imported"]

    def test_generation_env_override(self, tmp_path, monkeypatch):
        monkeypatch.setenv(seed.GENERATION_ENV, "build-host/x/y")
        assert seed.detect_generation() == "build-host/x/y"

    def test_cache_dir_precedence(self, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR, then the caller's path, then
        <checkout>/.jax_cache — never data_path, ~ or a temp name."""
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        monkeypatch.delenv(seed.CACHE_DIR_ENV, raising=False)
        assert seed.compile_cache_dir("/x") == "/x"
        assert seed.compile_cache_dir(None) == os.path.join(
            checkout, ".jax_cache")
        monkeypatch.setenv(seed.CACHE_DIR_ENV, "/env/dir")
        assert seed.compile_cache_dir("/x") == "/env/dir"
        assert seed.compile_cache_dir(None) == "/env/dir"
        # an empty variable is an unset one
        monkeypatch.setenv(seed.CACHE_DIR_ENV, "")
        assert seed.compile_cache_dir("/x") == "/x"


class TestNodeCacheResolution:
    """What a node does with the resolution: the directory does not
    follow data_path, and the standard variable is left to jax."""

    @pytest.fixture
    def config_updates(self, monkeypatch):
        """Re-arm the once-per-process cache set-up and record what it
        would set, without touching this process's real cache."""
        import jax

        from elasticsearch_tpu.search import tpu_service
        calls = []
        monkeypatch.setattr(tpu_service, "_cache_configured", False)
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: calls.append((name, value)))
        return calls

    def test_two_data_paths_resolve_to_one_directory(
            self, tmp_path, monkeypatch, config_updates):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.search import tpu_service
        monkeypatch.delenv(seed.CACHE_DIR_ENV, raising=False)
        resolved = []
        for name in ("a", "b"):
            monkeypatch.setattr(tpu_service, "_cache_configured", False)
            del config_updates[:]
            node = Node(str(tmp_path / name))
            node.close()
            resolved.append(dict(config_updates)
                            ["jax_compilation_cache_dir"])
        assert resolved[0] == resolved[1] == seed.compile_cache_dir()
        assert str(tmp_path) not in resolved[0]

    def test_explicit_setting_is_honoured(self, tmp_path, monkeypatch,
                                          config_updates):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.node import Node
        monkeypatch.delenv(seed.CACHE_DIR_ENV, raising=False)
        want = str(tmp_path / "explicit_cache")
        node = Node(str(tmp_path / "d"), settings=Settings.of(
            {"search.tpu_serving.compile_cache_dir": want}))
        node.close()
        assert dict(config_updates)["jax_compilation_cache_dir"] == want

    def test_env_dir_is_never_set_from_code(self, tmp_path, monkeypatch,
                                            config_updates):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.node import Node
        monkeypatch.setenv(seed.CACHE_DIR_ENV, str(tmp_path / "env_cache"))
        node = Node(str(tmp_path / "d"), settings=Settings.of(
            {"search.tpu_serving.compile_cache_dir": "/ignored"}))
        node.close()
        assert config_updates  # the set-up ran ...
        assert "jax_compilation_cache_dir" not in dict(config_updates)


class TestCli:
    def test_export_import_via_main(self, tmp_path, capsys):
        warm = _fake_cache(tmp_path)
        bundle = tmp_path / "seed.tar.gz"
        rc = seed.main(["export", "--cache-dir", str(warm),
                        "--out", str(bundle), "--generation", "g"])
        assert rc == 0
        assert "exported 3 artifact(s)" in capsys.readouterr().out
        cold = tmp_path / "cold"
        rc = seed.main(["import", str(bundle), "--cache-dir", str(cold),
                        "--generation", "g"])
        assert rc == 0
        assert "imported 3 artifact(s)" in capsys.readouterr().out


# ---------------------------------------------------------------------
# the acceptance bar: a fresh node booting from an imported seed pays
# zero live compiles for the seeded signature table
# ---------------------------------------------------------------------

_WARM_SCRIPT = r"""
import sys
import jax, jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

@jax.jit
def seeded_sig(x):
    return (x * 2.0 + 1.0).sum()

print(float(seeded_sig(jnp.arange(64, dtype=jnp.float32))))
"""


@pytest.mark.multiprocess
def test_seeded_node_pays_zero_live_compiles(tmp_path):
    # this jax build folds the cache-dir PATH into the cache key, so a
    # seed only replays when the fresh node resolves the same canonical
    # cache dir as the exporter — which compile_cache_dir guarantees
    # (identical default precedence on every host). Model that: warm
    # the canonical path, wipe it (fresh machine), import the seed back
    # into the same path, and demand zero new artifacts.
    import shutil
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(seed.CACHE_DIR_ENV, None)
    cache = tmp_path / "node_cache"
    cache.mkdir()

    def _run():
        return subprocess.run(
            [sys.executable, "-c", _WARM_SCRIPT, str(cache)],
            env=env, capture_output=True, text=True, timeout=240)

    proc = _run()
    assert proc.returncode == 0, proc.stderr
    artifacts = sorted(p.name for p in cache.iterdir())
    if not artifacts:
        pytest.skip("this jax build writes no persistent-cache "
                    "artifacts for CPU executables — cannot observe "
                    "compile replay (seed bundle round-trip is covered "
                    "by the synthetic tests above)")

    bundle = tmp_path / "seed.tar.gz"
    seed.export_bundle(str(cache), str(bundle), generation="test-gen")
    shutil.rmtree(cache)  # the fresh machine: same path, no cache
    summary = seed.import_bundle(str(bundle), str(cache),
                                 generation="test-gen")
    assert sorted(summary["imported"]) == artifacts

    before = {p.name for p in cache.iterdir()}
    proc = _run()
    assert proc.returncode == 0, proc.stderr
    after = {p.name for p in cache.iterdir()}
    # zero live compiles: the same signature produced NO new cache
    # entries — every executable came out of the seeded table
    assert after == before, (
        f"fresh node compiled live despite the seed: new artifacts "
        f"{sorted(after - before)}")

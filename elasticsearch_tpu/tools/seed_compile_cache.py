"""Export/import pre-seeded XLA compile-cache artifacts so a fresh
node's first-ever boot replays compiles instead of paying them live.

The persistent compile cache (``_ensure_compile_cache`` in
`search.tpu_service`) already makes *restarts* cheap — but the first
boot of a new machine still pays the full prewarm signature table in
live compiles. This tool closes that cold-boot residual: a warmed node
exports its cache directory as one seed bundle, keyed by its backend
generation; an init step imports the bundle on the new machine before
the node starts, and prewarm becomes a cache replay.

    python -m elasticsearch_tpu.tools.seed_compile_cache export \
        [--cache-dir DIR] [--out seed.tar.gz]
    python -m elasticsearch_tpu.tools.seed_compile_cache import \
        seed.tar.gz [--cache-dir DIR] [--force]

Generation keying: XLA cache entries are only valid for the backend
that produced them, so the manifest records ``<backend>/<jax version>/
<jaxlib version>`` and import refuses a mismatched bundle unless
``--force`` (or an explicit ``--generation`` override on either side —
the escape hatch for hosts where the device stack isn't importable at
packaging time, e.g. ``ES_TPU_CACHE_GENERATION`` in a build pipeline).

Import-light: jax is only imported to *detect* the local generation,
and failure to import degrades to the ``unknown`` generation rather
than an error.
"""

from __future__ import annotations

import argparse
import hashlib
import io
import json
import os
import sys
import tarfile
import time
from typing import Any, Dict, List, Optional

MANIFEST_NAME = "manifest.json"
BUNDLE_VERSION = 1

#: env override for the generation key (build hosts without jax)
GENERATION_ENV = "ES_TPU_CACHE_GENERATION"


#: the standard jax variable; when set, jax reads the directory from it
#: and the program sets none in code
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(path: Optional[str] = None) -> str:
    """The process's persistent-compile-cache directory, by the SAME
    precedence `_ensure_compile_cache` applies: JAX_COMPILATION_CACHE_DIR,
    then the caller's path (`search.tpu_serving.compile_cache_dir`), then
    `<checkout>/.jax_cache` next to the package. The directory is part of
    jax's cache key, so it must not follow `data_path`, `~` or a
    temporary name: a cache that moves never hits."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    if path:
        return path
    package_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_root), ".jax_cache")


def detect_generation() -> str:
    """``<backend>/<jax>/<jaxlib>`` of this host, or ``unknown`` when
    the device stack can't load (tools must run on build hosts too)."""
    env = os.environ.get(GENERATION_ENV)
    if env:
        return env
    try:
        import jax
        import jaxlib
        backend = jax.default_backend()
        return f"{backend}/{jax.__version__}/{jaxlib.__version__}"
    except Exception:  # noqa: BLE001 — degrade, never block packaging
        return "unknown"


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _cache_files(cache_dir: str) -> List[str]:
    """Relative paths of every artifact under the cache dir, sorted for
    a reproducible bundle."""
    out = []
    for root, _dirs, names in os.walk(cache_dir):
        for name in names:
            full = os.path.join(root, name)
            out.append(os.path.relpath(full, cache_dir))
    return sorted(out)


def export_bundle(cache_dir: str, out_path: str,
                  generation: Optional[str] = None) -> Dict[str, Any]:
    """Pack the cache dir into ``out_path`` (tar.gz with a manifest as
    its first member). Returns the manifest."""
    if not os.path.isdir(cache_dir):
        raise SystemExit(f"export: cache dir [{cache_dir}] does not exist "
                         f"— boot + prewarm a node against it first")
    rels = _cache_files(cache_dir)
    if not rels:
        raise SystemExit(f"export: cache dir [{cache_dir}] holds no "
                         f"artifacts — nothing to seed")
    manifest: Dict[str, Any] = {
        "bundle_version": BUNDLE_VERSION,
        "generation": generation or detect_generation(),
        "created_at": int(time.time()),
        "files": [{"name": rel,
                   "size": os.path.getsize(os.path.join(cache_dir, rel)),
                   "sha256": _sha256(os.path.join(cache_dir, rel))}
                  for rel in rels],
    }
    data = json.dumps(manifest, indent=2).encode("utf-8")
    with tarfile.open(out_path, "w:gz") as tar:
        info = tarfile.TarInfo(MANIFEST_NAME)
        info.size = len(data)
        info.mtime = manifest["created_at"]
        tar.addfile(info, io.BytesIO(data))
        for rel in rels:
            tar.add(os.path.join(cache_dir, rel), arcname=rel,
                    recursive=False)
    return manifest


def read_manifest(bundle_path: str) -> Dict[str, Any]:
    with tarfile.open(bundle_path, "r:gz") as tar:
        member = tar.getmember(MANIFEST_NAME)
        fh = tar.extractfile(member)
        if fh is None:
            raise SystemExit(f"import: [{bundle_path}] has no manifest")
        manifest = json.load(fh)
    if manifest.get("bundle_version") != BUNDLE_VERSION:
        raise SystemExit(
            f"import: bundle version "
            f"[{manifest.get('bundle_version')}] is not "
            f"[{BUNDLE_VERSION}]")
    return manifest


def import_bundle(bundle_path: str, cache_dir: str,
                  generation: Optional[str] = None,
                  force: bool = False) -> Dict[str, Any]:
    """Unpack a seed bundle into the cache dir. Refuses a generation
    mismatch unless `force`; existing artifacts are left alone (a live
    cache always wins over a seed). Returns a summary dict."""
    manifest = read_manifest(bundle_path)
    local_gen = generation or detect_generation()
    bundle_gen = manifest.get("generation", "unknown")
    if bundle_gen != local_gen and not force:
        raise SystemExit(
            f"import: bundle generation [{bundle_gen}] does not match "
            f"this host [{local_gen}] — seeded artifacts would never be "
            f"hit. Re-export on a matching host, or pass --force / "
            f"--generation to override.")
    os.makedirs(cache_dir, exist_ok=True)
    imported, skipped = [], []
    by_name = {f["name"]: f for f in manifest.get("files", [])}
    with tarfile.open(bundle_path, "r:gz") as tar:
        for member in tar.getmembers():
            if member.name == MANIFEST_NAME or not member.isfile():
                continue
            rel = os.path.normpath(member.name)
            if rel.startswith("..") or os.path.isabs(rel):
                raise SystemExit(
                    f"import: refusing path [{member.name}] escaping "
                    f"the cache dir")
            dest = os.path.join(cache_dir, rel)
            if os.path.exists(dest):
                skipped.append(rel)
                continue
            os.makedirs(os.path.dirname(dest) or cache_dir, exist_ok=True)
            src = tar.extractfile(member)
            with open(dest, "wb") as out:
                out.write(src.read())
            want = (by_name.get(member.name) or {}).get("sha256")
            if want and _sha256(dest) != want:
                os.unlink(dest)
                raise SystemExit(
                    f"import: checksum mismatch on [{member.name}] — "
                    f"corrupt bundle")
            imported.append(rel)
    return {"generation": bundle_gen, "imported": imported,
            "skipped": skipped}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m elasticsearch_tpu.tools.seed_compile_cache",
        description="Ship pre-seeded XLA compile-cache artifacts "
                    "between hosts, keyed per backend generation.")
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_exp = sub.add_parser("export", help="pack a warm cache dir into "
                                          "a seed bundle")
    p_exp.add_argument("--cache-dir", default=None,
                       help="cache dir to pack (default: the directory "
                            "a node compiles into)")
    p_exp.add_argument("--out", default="compile_cache_seed.tar.gz")
    p_exp.add_argument("--generation", default=None,
                       help="override the detected backend generation")
    p_imp = sub.add_parser("import", help="unpack a seed bundle into "
                                          "the cache dir")
    p_imp.add_argument("bundle")
    p_imp.add_argument("--cache-dir", default=None)
    p_imp.add_argument("--generation", default=None)
    p_imp.add_argument("--force", action="store_true",
                       help="import despite a generation mismatch")
    args = parser.parse_args(argv)

    # an explicit --cache-dir names the directory to pack/unpack; without
    # one the tool works on the directory a node would compile into
    cache_dir = args.cache_dir or compile_cache_dir()
    if args.cmd == "export":
        manifest = export_bundle(cache_dir, args.out,
                                 generation=args.generation)
        print(f"exported {len(manifest['files'])} artifact(s) "
              f"[generation {manifest['generation']}] "
              f"from {cache_dir} -> {args.out}")
        return 0
    summary = import_bundle(args.bundle, cache_dir,
                            generation=args.generation, force=args.force)
    print(f"imported {len(summary['imported'])} artifact(s) "
          f"[generation {summary['generation']}] into {cache_dir}"
          + (f"; {len(summary['skipped'])} already present"
             if summary["skipped"] else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())

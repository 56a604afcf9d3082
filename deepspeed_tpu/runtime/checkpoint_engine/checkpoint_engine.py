"""Pluggable checkpoint backends with crash-consistent commits.

Behavioural equivalent of reference ``deepspeed/runtime/checkpoint_engine/checkpoint_engine.py``
(``CheckpointEngine`` ABC) + ``torch_checkpoint_engine.py`` + ``nebula_checkpoint_engine.py``.
The default backend is Orbax/TensorStore, which natively writes *sharded, re-shardable* arrays —
this is what makes every checkpoint a "universal checkpoint" (reference
``checkpoint/universal_checkpoint.py``) by construction: restore may specify any sharding/mesh.

Commit protocol (crash consistency — see ``docs/FAULT_TOLERANCE.md``):

1. all tag data is staged into ``<save_dir>/<tag>.tmp/`` (``begin_tag``);
2. ``commit_tag`` drains async writes, computes a per-file SHA-256 manifest
   (``manifest.json``), fsyncs every staged file, and publishes the tag with a
   single ``os.rename(<tag>.tmp, <tag>)`` + parent-dir fsync;
3. the ``latest`` pointer is written (atomically, by the engine) only after the
   rename lands.

A kill at ANY point leaves either the previous committed tag intact (tmp dir
is garbage, ignored and reclaimed) or the new tag fully visible. ``load``
validates the manifest and raises :class:`CheckpointCorruptionError` naming the
first offending file; :func:`find_latest_committed_tag` falls back to the newest
tag whose manifest validates when the ``latest`` pointer is torn or stale.
"""

import hashlib
import json
import os
import pickle
import shutil
import time
from typing import Any, Dict, List, Optional

from ...utils.fault_injection import fault_point, retry_with_backoff
from ...utils.logging import logger

TMP_SUFFIX = ".tmp"
OLD_SUFFIX = ".old"       # graveyard for a re-saved tag's previous directory
MANIFEST_FILE = "manifest.json"
LATEST_FILE = "latest"


class CheckpointCorruptionError(RuntimeError):
    """A checkpoint failed manifest/checksum validation; the message names the
    offending file and the failure mode (missing / size / digest)."""


def _fsync_file(path: str):
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str):
    try:
        fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass  # some filesystems refuse dir fsync; the rename is still ordered
    finally:
        os.close(fd)


def _sha256_file(path: str, chunk: int = 1 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            blk = f.read(chunk)
            if not blk:
                break
            h.update(blk)
    return h.hexdigest()


def _walk_files(root: str) -> List[str]:
    """Relative paths of every regular file under ``root`` (sorted, manifest
    excluded)."""
    out = []
    for dirpath, _dirnames, filenames in os.walk(root):
        for name in filenames:
            rel = os.path.relpath(os.path.join(dirpath, name), root)
            if rel != MANIFEST_FILE:
                out.append(rel)
    return sorted(out)


def write_manifest(tag_dir: str, tag: str, fsync: bool = True) -> Dict[str, Any]:
    """Per-shard SHA-256 manifest over every file in ``tag_dir``. Written last
    (its presence marks a complete data set) and atomically (tmp + rename)."""
    files = {}
    for rel in _walk_files(tag_dir):
        full = os.path.join(tag_dir, rel)
        fault_point("ckpt.manifest.hash")
        files[rel] = {"sha256": _sha256_file(full),
                      "size": os.path.getsize(full)}
        if fsync:
            _fsync_file(full)
    manifest = {"version": 1, "tag": str(tag), "files": files,
                "committed_at": time.time()}
    tmp = os.path.join(tag_dir, MANIFEST_FILE + ".tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=2)
        f.flush()
        if fsync:
            os.fsync(f.fileno())
    os.rename(tmp, os.path.join(tag_dir, MANIFEST_FILE))
    if fsync:
        _fsync_dir(tag_dir)
    return manifest


def validate_manifest(tag_dir: str, strict: bool = False):
    """Validate every file in ``tag_dir`` against its manifest.

    Raises :class:`CheckpointCorruptionError` on a missing/truncated/corrupt
    file (named in the message). A missing manifest is tolerated with a warning
    (pre-manifest checkpoints) unless ``strict``.
    """
    mpath = os.path.join(tag_dir, MANIFEST_FILE)
    if not os.path.isfile(mpath):
        if strict:
            raise CheckpointCorruptionError(
                f"checkpoint {tag_dir} has no {MANIFEST_FILE} — it was never "
                "committed (torn write?)")
        logger.warning(f"[ckpt] {tag_dir} has no {MANIFEST_FILE}; skipping "
                       "integrity validation (pre-manifest checkpoint?)")
        return
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorruptionError(
            f"checkpoint manifest {mpath} is unreadable: {e}") from e
    for rel, meta in manifest.get("files", {}).items():
        full = os.path.join(tag_dir, rel)
        if not os.path.isfile(full):
            raise CheckpointCorruptionError(
                f"checkpoint {tag_dir}: shard {rel!r} is missing")
        size = os.path.getsize(full)
        if size != meta["size"]:
            raise CheckpointCorruptionError(
                f"checkpoint {tag_dir}: shard {rel!r} truncated "
                f"({size} bytes, manifest says {meta['size']})")
        if _sha256_file(full) != meta["sha256"]:
            raise CheckpointCorruptionError(
                f"checkpoint {tag_dir}: shard {rel!r} failed its SHA-256 "
                "checksum — the file is corrupt")


def is_committed_tag(save_dir: str, tag: str) -> bool:
    """A tag is committed iff its final directory exists with a readable
    manifest (tmp staging dirs are by definition uncommitted)."""
    tag_dir = os.path.join(save_dir, str(tag))
    if not os.path.isdir(tag_dir) or str(tag).endswith(TMP_SUFFIX) \
            or str(tag).endswith(OLD_SUFFIX):
        return False
    mpath = os.path.join(tag_dir, MANIFEST_FILE)
    if not os.path.isfile(mpath):
        # pre-manifest checkpoint: committed if the dir simply exists
        return True
    try:
        with open(mpath) as f:
            json.load(f)
        return True
    except (OSError, ValueError):
        return False


def find_latest_committed_tag(save_dir: str,
                              exclude: Optional[str] = None) -> Optional[str]:
    """Newest committed tag under ``save_dir`` by manifest commit time (file
    mtime fallback), skipping ``exclude`` and staging dirs — the automatic
    fallback when the ``latest`` pointer names a torn checkpoint."""
    best, best_t = None, -1.0
    try:
        entries = os.listdir(save_dir)
    except OSError:
        return None
    for name in entries:
        if name == str(exclude) or name.endswith(TMP_SUFFIX) \
                or name.endswith(OLD_SUFFIX):
            continue
        tag_dir = os.path.join(save_dir, name)
        mpath = os.path.join(tag_dir, MANIFEST_FILE)
        if not os.path.isfile(mpath):
            continue
        try:
            with open(mpath) as f:
                t = float(json.load(f).get("committed_at", 0.0))
        except (OSError, ValueError):
            continue
        t = t or os.path.getmtime(mpath)
        if t > best_t:
            best, best_t = name, t
    return best


def write_latest_pointer(save_dir: str, tag: str):
    """Atomic ``latest`` update: tmp + fsync + rename (a crash mid-update leaves
    the previous pointer intact)."""
    fault_point("ckpt.latest")
    tmp = os.path.join(save_dir, LATEST_FILE + ".tmp")
    with open(tmp, "w") as f:
        f.write(str(tag))
        f.flush()
        os.fsync(f.fileno())
    os.rename(tmp, os.path.join(save_dir, LATEST_FILE))
    _fsync_dir(save_dir)


class CheckpointEngine:
    """save/load/commit surface, mirroring the reference ABC, plus the atomic
    tag staging protocol (``begin_tag``/``commit_tag``)."""

    def __init__(self, config_params=None):
        self.config = config_params
        self._staging: Dict[str, str] = {}   # tag -> staged dir

    def create(self, tag: str):
        logger.info(f"[ckpt] start checkpoint {tag}")

    def save(self, state_dict: Any, path: str):
        raise NotImplementedError

    def load(self, path: str, map_location=None, template: Any = None,
             shardings: Any = None) -> Any:
        raise NotImplementedError

    def commit(self, tag: str) -> bool:
        logger.info(f"[ckpt] checkpoint {tag} ready")
        return True

    def makedirs(self, path: str, exist_ok: bool = True):
        os.makedirs(path, exist_ok=exist_ok)

    # ------------------------------------------------------------ atomic tags
    def staging_path(self, save_dir: str, tag: str) -> str:
        """Where ``begin_tag`` stages this tag's data. Non-zero ranks of a
        multi-host save use this (plus ``makedirs``) instead of ``begin_tag`` —
        only ONE rank may run the stale-staging reclaim, or ranks racing
        through ``begin_tag`` would rmtree each other's in-flight writes."""
        return os.path.join(save_dir, f"{tag}{TMP_SUFFIX}")

    def begin_tag(self, save_dir: str, tag: str) -> str:
        """Open a staging directory ``<save_dir>/<tag>.tmp`` for this tag's data
        (leftover staging from a crashed save is reclaimed). Call on ONE rank;
        peers join via ``staging_path`` after a barrier."""
        os.makedirs(save_dir, exist_ok=True)
        staged = self.staging_path(save_dir, tag)
        if os.path.isdir(staged):
            logger.warning(f"[ckpt] reclaiming stale staging dir {staged} "
                           "(previous save died mid-write)")
            shutil.rmtree(staged, ignore_errors=True)
        # a crash during a re-save of this tag can strand its graveyard copy
        grave = os.path.join(save_dir, f"{tag}{OLD_SUFFIX}")
        if os.path.isdir(grave):
            logger.warning(f"[ckpt] reclaiming stale graveyard dir {grave}")
            shutil.rmtree(grave, ignore_errors=True)
        os.makedirs(staged, exist_ok=True)
        self._staging[str(tag)] = staged
        self.create(tag)
        return staged

    def commit_tag(self, save_dir: str, tag: str) -> str:
        """Drain async writes, manifest + fsync the staged data, and publish the
        tag with one atomic rename. Returns the final tag directory."""
        staged = self._staging.pop(str(tag), None)
        if staged is None:
            staged = self.staging_path(save_dir, tag)
        if not os.path.isdir(staged):
            raise FileNotFoundError(
                f"commit_tag({tag!r}): no staged checkpoint at {staged} — "
                "begin_tag was never called or the staging dir was removed")
        # backend drain barrier (async orbax writes land before hashing)
        self.commit(tag)
        fault_point("ckpt.commit.manifest")
        write_manifest(staged, tag)
        final = os.path.join(save_dir, str(tag))
        if os.path.isdir(final):
            # re-saving an existing tag: replace it atomically-ish (rename to a
            # graveyard first so readers never see a half-deleted tag; a stale
            # graveyard left by a crash here is reclaimed by the next begin_tag
            # and ignored by tag discovery)
            grave = final + OLD_SUFFIX
            shutil.rmtree(grave, ignore_errors=True)
            os.rename(final, grave)
            shutil.rmtree(grave, ignore_errors=True)
        fault_point("ckpt.commit.rename")
        os.rename(staged, final)
        _fsync_dir(save_dir)
        logger.info(f"[ckpt] committed {tag} -> {final}")
        return final


class OrbaxCheckpointEngine(CheckpointEngine):
    """Array trees via Orbax (sharded + re-shardable); side metadata via JSON/pickle.

    ``save``/``load`` paths ending in ``.pkl``/``.json`` handle host-side state (scheduler,
    client state); other paths are treated as Orbax pytree directories. All writes
    go through :func:`retry_with_backoff` so transient I/O errors (flaky NFS/GCS
    fuse mounts) don't kill a training step that could have succeeded.
    """

    # transient-I/O retry policy (checkpoint writes are idempotent: orbax
    # force-overwrites and json/pkl rewrite whole files)
    IO_RETRIES = 2
    IO_BASE_DELAY = 0.05

    def __init__(self, config_params=None, use_async: bool = False):
        super().__init__(config_params)
        import orbax.checkpoint as ocp
        self._ocp = ocp
        self.use_async = use_async
        self._ckptr = ocp.StandardCheckpointer()

    def _retry(self, fn):
        return retry_with_backoff(fn, retries=self.IO_RETRIES,
                                  base_delay=self.IO_BASE_DELAY,
                                  retryable=(OSError,))

    def save(self, state_dict: Any, path: str):
        fault_point("ckpt.save")
        if path.endswith(".json"):
            def write_json():
                fault_point("ckpt.save.io")
                with open(path, "w") as f:
                    json.dump(state_dict, f, indent=2, default=str)
            self._retry(write_json)
            return
        if path.endswith(".pkl"):
            def write_pkl():
                fault_point("ckpt.save.io")
                with open(path, "wb") as f:
                    pickle.dump(state_dict, f)
            self._retry(write_pkl)
            return

        def write_tree():
            fault_point("ckpt.save.io")
            self._ckptr.save(os.path.abspath(path), state_dict, force=True)
            if not self.use_async:
                self._ckptr.wait_until_finished()
        self._retry(write_tree)
        # async_save: orbax's background thread drains the disk write while the
        # caller proceeds to the side-state writes/barrier; engine.save_checkpoint's
        # closing commit_tag() is the durability barrier, so the overlap is WITHIN
        # save_checkpoint (engine semantics require a durable checkpoint before
        # 'latest' advances — full resume-while-draining would defer commit to the
        # next save)

    def load(self, path: str, map_location=None, template: Any = None,
             shardings: Any = None) -> Any:
        fault_point("ckpt.load")
        if path.endswith(".json"):
            def read_json():
                fault_point("ckpt.load.io")
                with open(path) as f:
                    return json.load(f)
            return self._retry(read_json)
        if path.endswith(".pkl"):
            def read_pkl():
                fault_point("ckpt.load.io")
                with open(path, "rb") as f:
                    return pickle.load(f)
            return self._retry(read_pkl)
        import jax
        if template is not None:
            abstract = jax.tree_util.tree_map(
                lambda l, s=None: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                       sharding=s) if hasattr(l, "shape") else l,
                template)
            if shardings is not None:
                abstract = jax.tree_util.tree_map(
                    lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s)
                    if hasattr(l, "shape") else l,
                    template, shardings)
            return self._retry(
                lambda: self._ckptr.restore(os.path.abspath(path), abstract))
        return self._retry(lambda: self._ckptr.restore(os.path.abspath(path)))

    def load_subtree(self, path: str, key: str, template: Any, shardings: Any = None):
        """Restore one top-level entry (e.g. just ``params``) from a full training
        checkpoint without materialising the rest (optimizer state etc.) — the inference
        engine's sharded-load path."""
        import jax
        ocp = self._ocp
        if shardings is not None:
            abstract = jax.tree_util.tree_map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s)
                if hasattr(l, "shape") else l, template, shardings)
        else:
            abstract = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype)
                if hasattr(l, "shape") else l, template)
        abspath = os.path.abspath(path)
        restore = self._ocp.args.PyTreeRestore(item={key: abstract},
                                               partial_restore=True)
        with ocp.PyTreeCheckpointer() as ckptr:
            restored = ckptr.restore(abspath, args=restore)
        return restored[key]

    def commit(self, tag: str) -> bool:
        self._ckptr.wait_until_finished()
        return super().commit(tag)


def make_checkpoint_engine(checkpoint_config=None) -> CheckpointEngine:
    use_async = bool(getattr(checkpoint_config, "async_save", False))
    return OrbaxCheckpointEngine(checkpoint_config, use_async=use_async)

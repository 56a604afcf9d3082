"""What device this process runs on, and how it is set up to compile for it.

One home for the facts every entry point needs about the accelerator:

- :data:`PEAKS` — published per-chip peaks keyed by ``device_kind``, the one
  table MFU and roofline shares divide by;
- :func:`enable_compile_cache` — where the persistent compilation cache goes;
- :func:`claims_chips` — the one-process-per-chip rule child spawners apply;
- :func:`pallas_interpret` — whether Pallas kernels run compiled (TPU) or in
  the interpreter (any other backend), decided once and said out loud.
"""

import functools
import os
from typing import Dict

import jax

from .logging import logger

#: Per-chip peaks by ``jax.devices()[0].device_kind``. Source: Google Cloud
#: documentation, "TPU v5e" system architecture page (197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s chip-to-chip ICI).
#: A kind that is not here has no published number in this tree: add it with
#: its source, do not default it.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbytes_per_s": 819.0, "hbm_gbytes": 16.0,
                    "ici_gbits_per_s": 1600.0},
}

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: git-ignored, at the root of the checkout; fixed because the cache keys on it
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def device_peaks(device_kind: str = None) -> Dict[str, float]:
    """Published peaks of ``device_kind`` (default: this process's device 0).
    Raises ``KeyError`` for a kind the table does not hold — a bench or smoke
    run on an unknown device must not publish a utilisation."""
    kind = device_kind or jax.devices()[0].device_kind
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {kind!r} in "
                       f"deepspeed_tpu.utils.device.PEAKS (has {sorted(PEAKS)})")
    return PEAKS[kind]


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax has already read it — use it and
    write nothing. Unset: :data:`DEFAULT_CACHE_DIR`, the same path from every
    process of this checkout (never a temp name, pid or time), so a second
    process — a child, or the next run — finds what the first compiled.
    Called by ``ds.initialize``, ``ds.init_inference`` and the entry scripts
    before their first compile; idempotent. Also makes the names of the
    programs' device scopes part of the cache's key."""
    _key_the_cache_on_the_scopes()
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


@functools.cache
def _key_the_cache_on_the_scopes() -> None:
    """jax keys a cached executable on its program WITHOUT op names and
    locations, and an executable carries the op metadata it was compiled
    with: a program that differs from a cached one only in its declared
    scopes (``observability.scope``) would be loaded with the other's names,
    and a profiler trace would show regions the source does not have. jax's
    own hook for additions to the key takes the digest of the scopes' call
    sites, so a change to them compiles once more and nothing else does."""
    from jax._src import cache_key

    from ..observability.schema import scope_sites_digest
    digest = scope_sites_digest()
    cache_key.custom_hook = lambda: "deepspeed_tpu scopes " + digest


def claims_chips(env) -> bool:
    """Would a jax process started with environment ``env`` claim this host's
    accelerator chips? Anything but an explicit ``JAX_PLATFORMS=cpu`` does.

    libtpu hands a host's chips to ONE process at a time: a second process
    that initialises jax then fails or hangs. So every place that starts jax
    children on the local host asks this first and refuses when it is true —
    one process drives all local chips (through the mesh, or as in-process
    one-chip replicas), and a child that needs chips runs on its own host."""
    return env.get("JAX_PLATFORMS", "").strip().lower() != "cpu"


@functools.cache
def pallas_interpret() -> bool:
    """True when Pallas kernels must run in interpret mode (no TPU backend).
    Says so once: a process that expected a chip and landed on the CPU is
    otherwise indistinguishable from a slow one."""
    interpret = jax.default_backend() != "tpu"
    if interpret:
        logger.warning(
            f"Pallas kernels run in INTERPRET mode (backend "
            f"{jax.default_backend()!r}, not tpu): correctness only, no "
            "Mosaic lowering is exercised")
    return interpret

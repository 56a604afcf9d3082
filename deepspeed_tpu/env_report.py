"""Environment report — the ``ds_report`` equivalent.

Reference ``deepspeed/env_report.py`` (``main:147``, op-compatibility table): prints
framework/toolchain versions, the device inventory as JAX sees it, and the build status of
the host-side native ops (the TPU analogue of the reference's CUDA op table — device kernels
need no prebuild here, XLA/Pallas compile in-process).
"""

import importlib
import shutil
import subprocess
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[93m[NO]\033[0m"


def _version(mod_name: str) -> str:
    try:
        mod = importlib.import_module(mod_name)
        return getattr(mod, "__version__", "unknown")
    except Exception:
        return "not installed"


def _gxx_version() -> str:
    gxx = shutil.which("g++")
    if not gxx:
        return "not found"
    try:
        out = subprocess.run([gxx, "--version"], capture_output=True, text=True,
                             timeout=10).stdout.splitlines()
        return out[0] if out else "unknown"
    except Exception:
        return "unknown"


def main() -> int:
    print("-" * 70)
    print("deepspeed_tpu environment report (ds_report)")
    print("-" * 70)
    print("versions:")
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy",
                "ml_dtypes"):
        print(f"  {mod:<18} {_version(mod)}")
    print(f"  {'python':<18} {sys.version.split()[0]}")
    print(f"  {'g++':<18} {_gxx_version()}")

    print("devices:")
    import jax
    devs = jax.devices()            # no backend: this raises, and so should we
    print(f"  platform={devs[0].platform} device_count={len(devs)} "
          f"process={jax.process_index()}/{jax.process_count()}")
    for d in devs[:8]:
        lim = (d.memory_stats() or {}).get("bytes_limit")
        mem = f" hbm={lim / 1024**3:.1f}GB" if lim else ""
        print(f"  {d.id}: {d.device_kind}{mem}")
    if len(devs) > 8:
        print(f"  ... and {len(devs) - 8} more")

    _print_ops_table()
    return 0


def _print_ops_table():
    print("host-side native ops (op_builder):")
    from .ops.adam.cpu_adam import native_available
    print(f"  cpu_adam/cpu_adagrad (SIMD offload step) "
          f"{GREEN_OK if native_available() else RED_NO}")
    try:
        from .runtime.swap_tensor.aio import aio_available
        print(f"  async_io (NVMe swap) {GREEN_OK if aio_available() else RED_NO}")
    except ImportError:
        print(f"  async_io (NVMe swap) {RED_NO}")
    print("-" * 70)


if __name__ == "__main__":
    sys.exit(main())

"""Real-TPU compiled-kernel correctness (skipped on CPU, where kernels run in
interpreter mode and a Mosaic regression would go unseen).

The check bodies and tolerances live in ``deepspeed_tpu.ops.kernel_checks`` — the
SAME table ``chip_smoke.py``'s kernel gate runs whole on the chip, so the test lane
and that gate cannot drift.

Run on a TPU host with ``python -m pytest tests/unit/ops/test_kernels_tpu.py -p
no:cacheprovider`` OUTSIDE the CPU-pinning conftest, or drive via
``python tests/unit/ops/test_kernels_tpu.py`` directly.
"""

import pytest

import jax

from deepspeed_tpu.ops.kernel_checks import KERNEL_CHECKS, run_kernel_checks

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="compiled-kernel checks need a TPU")


@pytest.mark.parametrize("name", sorted(KERNEL_CHECKS))
def test_kernel_compiled(name):
    errs = run_kernel_checks([name])
    assert name in errs


def test_ring_kernel_compiled():
    """Ring attention is mesh-level (not in the single-chip gate): compiled run
    over a 1-device seq mesh must match XLA."""
    import numpy as np
    import jax.numpy as jnp
    from deepspeed_tpu.ops.attention.ring import ring_attention
    from deepspeed_tpu.ops.transformer.attention import xla_attention
    from deepspeed_tpu.parallel.mesh import MeshSpec, set_global_mesh
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
               for _ in range(3))
    set_global_mesh(MeshSpec({"seq": 1}, jax.devices()[:1]))
    try:
        o1 = jax.jit(lambda *a: ring_attention(*a, causal=True))(q, k, v)
    finally:
        set_global_mesh(None)
    o2 = xla_attention(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(o1 - o2)))
    assert err < 0.02, err


if __name__ == "__main__":
    for name in sorted(KERNEL_CHECKS):
        errs = run_kernel_checks([name])
        print(f"{name}: max abs err {errs[name]:.5f} OK")
    test_ring_kernel_compiled()
    print("ring: OK")

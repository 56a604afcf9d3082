"""Ulysses attention — all-to-all sequence parallelism over the ``seq`` mesh axis.

The second sequence-parallel strategy (DeepSpeed-Ulysses, Jacobs et al. 2023; absent
from the reference snapshot like ring — SURVEY §2.3): activations arrive sharded on the
SEQUENCE dim; an in-graph ``all_to_all`` re-shards them onto the HEAD dim, every device
then runs ordinary full-sequence attention for its ``h/P`` heads, and a second
``all_to_all`` restores sequence sharding. Communication is 2 all-to-alls of the qkv/o
activations (O(bt·h·d/P) per device, constant in P on a torus) versus ring's P
``ppermute`` steps of K/V — Ulysses wins when heads divide nicely and the per-device
full-sequence attention fits; ring wins for extreme lengths. Both ride ICI.

Requires ``n_heads % seq_axis == 0`` (the Ulysses constraint); falls back to ring
otherwise.
"""

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...parallel.mesh import AXIS_SEQ, BATCH_AXES, get_global_mesh
from ...utils.jax_compat import shard_map


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True, mask: Optional[jnp.ndarray] = None,
                      softmax_scale: Optional[float] = None,
                      dropout_rate: float = 0.0, dropout_rng=None,
                      axis_name: str = AXIS_SEQ, mesh_spec=None) -> jnp.ndarray:
    """Drop-in attention: q/k/v ``(b, t, h, d)`` with ``t`` sharded over ``seq``."""
    from .ring import ring_attention
    mesh = mesh_spec or get_global_mesh()
    if mesh is None or mesh.size(axis_name) <= 1 or mask is not None \
            or dropout_rate > 0.0:
        from .flash import flash_attention
        return flash_attention(q, k, v, causal=causal, mask=mask,
                               softmax_scale=softmax_scale,
                               dropout_rate=dropout_rate, dropout_rng=dropout_rng)
    b, t, h, d = q.shape
    S = mesh.size(axis_name)
    if h % S != 0:
        # Ulysses needs head divisibility; ring has no such constraint
        return ring_attention(q, k, v, causal=causal,
                              softmax_scale=softmax_scale, axis_name=axis_name,
                              mesh_spec=mesh)
    if not (t % S == 0):
        raise AssertionError(f"seq len {t} must divide the seq axis {S}")
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(d))

    def ulysses_fn(q_l, k_l, v_l):
        # local (b, t/S, h, d) → all_to_all → (b, t, h/S, d): scatter the head dim,
        # gather the sequence dim
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                      tiled=True)

        qh, kh, vh = seq_to_heads(q_l), seq_to_heads(k_l), seq_to_heads(v_l)
        # full-sequence attention over the local head group (fused by XLA; the MXU
        # sees the complete t×t problem for h/S heads)
        s = jnp.einsum("bthd,bshd->bhts", qh.astype(jnp.float32),
                       kh.astype(jnp.float32)) * scale
        if causal:
            tri = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(tri[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(vh.dtype)
        o = jnp.einsum("bhts,bshd->bthd", p, vh)
        return heads_to_seq(o).astype(q_l.dtype)

    # manual over the batch axes too: the body is per-example, and leaving
    # them to GSPMD around the all_to_all makes a partially-manual region
    # (XLA's partitioner aborts on it: "Invalid binary instruction opcode copy")
    batch_axes = tuple(ax for ax in BATCH_AXES if mesh.size(ax) > 1)
    bsz = int(np.prod([mesh.size(ax) for ax in batch_axes])) if batch_axes else 1
    if b % bsz:
        batch_axes = ()
    spec = P(batch_axes or None, axis_name, None, None)
    mapped = shard_map(
        ulysses_fn,
        mesh=mesh.mesh,
        axis_names=set(batch_axes) | {axis_name},
        in_specs=(spec,) * 3,
        out_specs=spec,
        check_vma=False,
    )
    return mapped(q, k, v)

"""The selective scan (Mamba-1, Gu & Dao, arXiv 2312.00752): a recurrence whose
decay differs by channel AND by state lane,

    S_t = exp(dt_t[None, :] * A) * S_{t-1} + B_t[:, None] * (dt_t * x_t)[None, :]
    y_t = C_t . S_t + D * x_t

per sequence, with ``S`` ``(n, c)`` float32: ``n`` state lanes on the
sublanes, the ``c`` channels on the lanes. ``A`` is ``(n, c)`` (negative),
``x`` and ``dt`` ``(b, t, c)`` (``dt`` after its softplus, 0 at the padding of
a right-padded row: ``exp(0 A) = 1`` and ``0 x = 0``, so padding neither
decays nor feeds the state and the state after the bucket is the state after
the last real token), ``B`` and ``C`` ``(b, t, n)``, ``D`` ``(c,)``.
Mamba-2's chunked form (``models/mamba2.py: ssd_chunked``) rests on ONE decay
a head and does not apply.

- :func:`selective_scan` (prefill): a Mosaic kernel, grid ``(b, channel tiles,
  time blocks)`` with the time blocks innermost and the state of a channel
  tile in VMEM scratch across them; inside a block the steps run one after
  another, eight to a store. Everything is float32.
- :func:`selective_scan_xla`: the same in ``jax.numpy``, an associative scan
  inside blocks of time and a ``lax.scan`` between them: what the kernel is
  tested against and was measured against on the chip.
- :func:`selective_step` (decode): the one-token update of ``(b, n, c)``, a
  kernel a sequence (for its rounding: its docstring); :func:`selective_step_xla`
  the plain form.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.device import pallas_interpret as _interpret

#: channels a kernel tile holds, time steps a block, steps a store
TILE_C = 512
BLOCK_T = 128
GROUP = 8


def selective_step_xla(state, x, dt, A, B, C, D):
    """One token, ``jax.numpy`` form. ``state`` (b, n, c) float32; ``x``, ``dt``
    (b, c); ``A`` (n, c); ``B``, ``C`` (b, n); ``D`` (c,). Returns ``(y (b, c),
    new state)``."""
    new = state * jnp.exp(dt[:, None, :] * A) + B[:, :, None] * (dt * x)[:, None, :]
    return jnp.sum(new * C[:, :, None], axis=1) + D * x, new


def _step_kernel(s_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, o_ref):
    """One sequence's update: ``s`` ``(1, n, c)``; ``x``, ``dt``, ``y`` ``(1, 1,
    c)``; ``b``, ``c`` ``(1, n, 128)`` (a column the lanes repeat); ``a`` ``(n,
    c)``; ``d`` ``(1, c)``."""
    reps = s_ref.shape[-1] // b_ref.shape[-1]

    def lanes(col):
        return col if reps == 1 else jnp.concatenate([col] * reps, axis=1)

    x, dt = x_ref[0], dt_ref[0]
    new = jnp.exp(dt * a_ref[...]) * s_ref[0] + lanes(b_ref[0]) * (dt * x)
    o_ref[0] = new
    y_ref[0] = jnp.sum(new * lanes(c_ref[0]), axis=0, keepdims=True) + d_ref[...] * x


@jax.jit
def selective_step(state, x, dt, A, B, C, D):
    """One token (decode), operands and results as :func:`selective_step_xla`,
    as a Mosaic kernel a sequence. Not for its speed (the update moves the
    state once either way) but for its ROUNDING: a kernel's body is compiled
    alone, so the serving chunk and ``engine.generate``'s loop compute ``y``
    bit for bit alike, where XLA's fusion summed the state lanes in an order
    that depended on the program around the step (on the chip the two then
    parted by one bfloat16 step in a few hundred of a layer's outputs, first
    behind a Mamba-1 mixer every time, and a random stand-in's near ties
    turned that into other tokens: PERF.md section 6, PR 59). The state is
    updated in place."""
    b, n, c = state.shape
    width = min(128, c)
    row = pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0))
    col = pl.BlockSpec((1, n, width), lambda i: (i, 0, 0))
    whole = pl.BlockSpec((1, n, c), lambda i: (i, 0, 0))
    f32 = jnp.float32
    y, new = pl.pallas_call(
        _step_kernel,
        grid=(b,),
        in_specs=[whole, row, row, pl.BlockSpec((n, c), lambda i: (0, 0)), col, col,
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=[row, whole],
        out_shape=[jax.ShapeDtypeStruct((b, 1, c), f32),
                   jax.ShapeDtypeStruct((b, n, c), f32)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="selective_step",
        interpret=_interpret(),
    )(state.astype(f32), x.astype(f32)[:, None], dt.astype(f32)[:, None], A.astype(f32),
      jnp.broadcast_to(B.astype(f32)[..., None], (b, n, width)),
      jnp.broadcast_to(C.astype(f32)[..., None], (b, n, width)),
      D.astype(f32).reshape(1, c))
    return y[:, 0], new


def selective_scan_xla(x, dt, A, B, C, D, block: int = 64):
    """``jax.numpy`` form from a zero state: ``(y (b, t, c), state (b, n, c))``."""
    b, t, c = x.shape
    n = A.shape[0]
    Q = min(block, t)
    pad = (-t) % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (x, dt, B, C))
    blocks = (t + pad) // Q

    def by_block(a):
        return a.reshape(b, blocks, Q, a.shape[-1]).transpose(1, 0, 2, 3)

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    def one(state, inp):
        xq, dtq, Bq, Cq = inp                                   # (b, Q, .)
        decay = jnp.exp(dtq[:, :, None, :] * A)                 # (b, Q, n, c)
        fed = Bq[..., None] * (dtq * xq)[:, :, None, :]
        cum, own = jax.lax.associative_scan(combine, (decay, fed), axis=1)
        states = cum * state[:, None] + own
        y = jnp.sum(states * Cq[..., None], axis=2) + D * xq
        return states[:, -1], y

    state, y = jax.lax.scan(one, jnp.zeros((b, n, c), jnp.float32),
                            tuple(by_block(a) for a in (x, dt, B, C)))
    return y.transpose(1, 0, 2, 3).reshape(b, t + pad, c)[:, :t], state


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, s_ref, state, *,
                 block_t, tile_c):
    """One time block of one channel tile. ``x``/``dt``/``y`` blocks ``(1,
    block_t, tile_c)``; ``b``/``c`` blocks ``(1, block_t, n, 128)``, a step's
    ``n`` values down the sublanes and the same in every lane; ``a`` ``(n,
    tile_c)``; ``d`` ``(1, tile_c)``; ``s`` (the state after the last block)
    ``(1, n, tile_c)``; ``state`` the scratch the blocks hand on."""
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    A = a_ref[...]
    Dv = d_ref[...]
    reps = tile_c // b_ref.shape[-1]

    def lanes(col):                       # (n, 128) -> (n, tile_c)
        return col if reps == 1 else jnp.concatenate([col] * reps, axis=1)

    def group(g, S):
        r0 = pl.multiple_of(g * GROUP, GROUP)
        x8 = x_ref[0, pl.ds(r0, GROUP), :]
        dt8 = dt_ref[0, pl.ds(r0, GROUP), :]
        ys = []
        for i in range(GROUP):
            xi, dti = x8[i:i + 1, :], dt8[i:i + 1, :]
            S = jnp.exp(dti * A) * S + lanes(b_ref[0, r0 + i]) * (dti * xi)
            ys.append(jnp.sum(S * lanes(c_ref[0, r0 + i]), axis=0, keepdims=True)
                      + Dv * xi)
        y_ref[0, pl.ds(r0, GROUP), :] = jnp.concatenate(ys, axis=0)
        return S

    S = jax.lax.fori_loop(0, block_t // GROUP, group, state[...])
    state[...] = S

    @pl.when(tb == pl.num_programs(2) - 1)
    def _last():
        s_ref[0] = S


def _tile(c: int) -> int:
    """Channels a tile: ``TILE_C`` where whole tiles of it divide ``c``, else
    the largest multiple of 128 that does, else all of ``c``."""
    for tile in (TILE_C, 256, 128):
        if c % tile == 0:
            return tile
    return c


@functools.partial(jax.jit, static_argnames=("block_t",))
def selective_scan(x, dt, A, B, C, D, block_t: int = BLOCK_T):
    """The kernel, from a zero state: ``(y (b, t, c), state (b, n, c))``, all
    float32. ``t`` is padded to whole blocks with ``dt = 0`` (which leaves the
    state as it is) and the padding's ``y`` cut off."""
    b, t, c = x.shape
    n = A.shape[0]
    bt = min(block_t, -(-t // GROUP) * GROUP)
    pad = (-t) % bt
    x, dt, B, C = (a.astype(jnp.float32) for a in (x, dt, B, C))
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (x, dt, B, C))
    T = t + pad
    tile = _tile(c)
    width = min(128, tile)
    # a step's n values as a column the lanes repeat: what the state's rows
    # are multiplied by, with no transpose inside the kernel
    Bc = jnp.broadcast_to(B[..., None], (b, T, n, width))
    Cc = jnp.broadcast_to(C[..., None], (b, T, n, width))
    seq = pl.BlockSpec((1, bt, tile), lambda i, j, k: (i, k, j))
    col = pl.BlockSpec((1, bt, n, width), lambda i, j, k: (i, k, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, block_t=bt, tile_c=tile),
        grid=(b, c // tile, T // bt),
        in_specs=[seq, seq, pl.BlockSpec((n, tile), lambda i, j, k: (0, j)), col, col,
                  pl.BlockSpec((1, tile), lambda i, j, k: (0, j))],
        out_specs=[seq, pl.BlockSpec((1, n, tile), lambda i, j, k: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((b, T, c), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="selective_scan",
        interpret=_interpret(),
    )(x, dt, A.astype(jnp.float32), Bc, Cc, D.astype(jnp.float32).reshape(1, c))
    return y[:, :t], state

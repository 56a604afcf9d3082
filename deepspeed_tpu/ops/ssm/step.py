"""One-token update of a state-space layer's recurrent state (decode) where the
decay is ONE number a head (Mamba-2; a decay that differs by state lane, Mamba-1's,
is ``selective_scan.selective_step``).

    h' = exp(dt A) h + (dt x) (x) B          y = C . h' + D x

per sequence and head; ``h`` is ``(b, heads, head_dim, state)`` float32 and is
read and written once a step: the update is bound by the memory, so its floor
is twice the state's bytes over the chip's bytes/s.
"""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def ssm_step_xla(state, x, dt, A, B, C, D):
    """``jax.numpy`` form. ``state`` (b, h, p, n) float32; ``x`` (b, h, p);
    ``dt`` (b, h); ``A``/``D`` (h,); ``B``/``C`` (b, g, n), a group serving
    ``h // g`` consecutive heads. Returns ``(y (b, h, p), new state)``."""
    h, g = x.shape[1], B.shape[1]
    Bh = jnp.repeat(B, h // g, axis=1)
    Ch = jnp.repeat(C, h // g, axis=1)
    new = state * jnp.exp(dt * A)[..., None, None] \
        + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch, precision=HI) + D[:, None] * x
    return y, new


def ssm_step(state, x, dt, A, B, C, D):
    return ssm_step_xla(state, x, dt, A, B, C, D)

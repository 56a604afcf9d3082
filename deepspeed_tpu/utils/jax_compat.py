"""One spelling of ``jax.shard_map`` for the framework's mesh convention.

Every mesh here carries all six named axes (``parallel/mesh.py``), most of
them size 1. Callers name the axes their body is manual over
(``axis_names``); jax leaves the rest to GSPMD. An axis of size 1 is the same
program manual or auto, but jax only runs a partially-manual region under
``jit`` — called eagerly it rejects the specs. So size-1 axes are folded into
the manual set here: a region whose remaining axes are all size 1 becomes
fully manual and runs eagerly as well as under ``jit``; one that leaves a real
(size > 1) axis to GSPMD still needs ``jit``, which is jax's own rule.
"""

from typing import Any, Optional, Set

import jax


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: Optional[Set[Any]] = None, check_vma: bool = False):
    if axis_names is not None:
        axis_names = set(axis_names) | {
            ax for ax, size in mesh.shape.items() if size == 1}
        if axis_names == set(mesh.axis_names):
            axis_names = None
    kwargs = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma, **kwargs)

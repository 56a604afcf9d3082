"""Consolidate a (possibly ZeRO-sharded) checkpoint into one fp32 state dict.

Behavioural equivalent of reference ``deepspeed/utils/zero_to_fp32.py`` (the script users
run to turn per-rank ZeRO shards into a plain ``pytorch_model.bin``). Orbax checkpoints
are re-shardable by construction, so "consolidation" is a restore with replicated
(host) sharding followed by a flat fp32 dump — no shard-merging arithmetic needed.

CLI: ``python -m deepspeed_tpu.utils.zero_to_fp32 <checkpoint_dir> <output_file>``
(``checkpoint_dir`` is the engine save dir or a specific ``global_stepN`` inside it).
Output format by extension: ``.npz`` (numpy), ``.pt`` (torch state dict), default npz.
"""

import argparse
import os
import sys
from typing import Any, Dict

import numpy as np

from .logging import logger


def _flatten_params(tree: Any) -> Dict[str, np.ndarray]:
    import jax
    out: Dict[str, np.ndarray] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = ".".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[name] = np.asarray(leaf, dtype=np.float32)
    return out


def get_fp32_state_dict_from_zero_checkpoint(checkpoint_dir: str,
                                             tag: str = None) -> Dict[str, np.ndarray]:
    """Reference ``get_fp32_state_dict_from_zero_checkpoint``: returns a flat
    name → fp32 numpy array dict of the model parameters."""
    path = checkpoint_dir
    latest = os.path.join(checkpoint_dir, "latest")
    if tag is not None:
        path = os.path.join(checkpoint_dir, tag)
    elif os.path.isfile(latest):
        with open(latest) as f:
            path = os.path.join(checkpoint_dir, f.read().strip())
    state_path = os.path.join(path, "state")
    if not os.path.isdir(state_path):
        raise FileNotFoundError(f"no engine state at {state_path}")
    # Restore with explicit single-device shardings built from checkpoint METADATA —
    # the consolidator typically runs on a different (often 1-device) topology than
    # the training mesh that wrote the checkpoint, so the saved shardings must not
    # be replayed (this is the whole point of consolidation).
    import jax
    import orbax.checkpoint as ocp
    ckptr = ocp.StandardCheckpointer()
    meta = ckptr.metadata(os.path.abspath(state_path))
    host = jax.local_devices()[0]
    sharding = jax.sharding.SingleDeviceSharding(host)

    def abstract(m):
        return jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=sharding)

    is_meta_leaf = lambda x: hasattr(x, "shape") and hasattr(x, "dtype")
    params_meta = dict(meta.item_metadata)["params"]
    abstract_params = jax.tree_util.tree_map(abstract, params_meta,
                                             is_leaf=is_meta_leaf)
    restore_args = jax.tree_util.tree_map(
        lambda _: ocp.ArrayRestoreArgs(sharding=sharding), params_meta,
        is_leaf=is_meta_leaf)
    restore = ocp.args.PyTreeRestore(
        item={"params": abstract_params},
        restore_args={"params": restore_args},
        partial_restore=True)
    with ocp.PyTreeCheckpointer() as tree_ckptr:
        restored = tree_ckptr.restore(os.path.abspath(state_path), args=restore)
    return _flatten_params(restored["params"])


def convert_zero_checkpoint_to_fp32_state_dict(checkpoint_dir: str,
                                               output_file: str,
                                               tag: str = None):
    """Reference ``convert_zero_checkpoint_to_fp32_state_dict``."""
    sd = get_fp32_state_dict_from_zero_checkpoint(checkpoint_dir, tag)
    n_params = sum(int(v.size) for v in sd.values())
    if output_file.endswith(".pt") or output_file.endswith(".bin"):
        import torch
        torch.save({k: torch.from_numpy(np.ascontiguousarray(v))
                    for k, v in sd.items()}, output_file)
    else:
        np.savez(output_file if output_file.endswith(".npz")
                 else output_file + ".npz", **sd)
    logger.info(f"consolidated {len(sd)} tensors / {n_params:,} fp32 params "
                f"-> {output_file}")
    return sd


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Consolidate a deepspeed_tpu checkpoint into one fp32 state dict")
    p.add_argument("checkpoint_dir")
    p.add_argument("output_file", help=".npz (numpy) or .pt/.bin (torch)")
    p.add_argument("--tag", default=None, help="checkpoint tag (default: latest)")
    args = p.parse_args(argv)
    convert_zero_checkpoint_to_fp32_state_dict(args.checkpoint_dir,
                                               args.output_file, args.tag)
    return 0


if __name__ == "__main__":
    sys.exit(main())

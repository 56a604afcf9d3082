"""Sharded state-dict loading.

Behavioural equivalent of reference ``deepspeed/runtime/state_dict_factory.py``
(``SDLoaderFactory:20``, ``MegatronSDLoader:214``, merge/split by MP degree) +
``module_inject/load_checkpoint.py``: big checkpoints arrive as MANY files (HF
``pytorch_model-0000x-of-0000N.bin`` / ``model-*.safetensors`` with an index json, or a
Megatron ``mp_rank_XX`` list); loading must stream shard-by-shard, never materialising
the full model on host — the reference's AutoTP/sharded-load requirement (a 7B
BLOOM checkpoint does not fit a host-side copy next to its device copy).

Design: a :class:`ShardedStateDict` is a lazy mapping name → tensor backed by the shard
index; tensors load on first access, and ``release_shard`` drops whole files once their
tensors are consumed. ``merge``/``split`` helpers re-partition query/key/value or
row/column-parallel weights across MP degrees (the MegatronSDLoader merge_state_dict /
split_state_dict capability) as pure numpy ops.
"""

import json
import os
from typing import Any, Dict, List, Optional

import numpy as np

from ..utils.logging import logger


class ShardedStateDict:
    """Lazy name → numpy tensor view over a sharded checkpoint directory.

    Supports: HF torch shards with ``pytorch_model.bin.index.json``, HF safetensors
    shards with ``model.safetensors.index.json``, single-file ``pytorch_model.bin`` /
    ``model.safetensors``.
    """

    def __init__(self, path: str):
        self.path = path
        self.weight_map: Dict[str, str] = {}
        self._cache: Dict[str, Dict[str, Any]] = {}   # shard file -> loaded dict
        self._format: Optional[str] = None
        self._resolve(path)

    # ------------------------------------------------------------------ resolve
    def _resolve(self, path: str):
        candidates = [
            ("pytorch_model.bin.index.json", "torch"),
            ("model.safetensors.index.json", "safetensors"),
        ]
        for idx_name, fmt in candidates:
            idx_path = os.path.join(path, idx_name)
            if os.path.isfile(idx_path):
                with open(idx_path) as f:
                    index = json.load(f)
                self.weight_map = dict(index["weight_map"])
                self._format = fmt
                logger.info(f"[state_dict] sharded {fmt} checkpoint: "
                            f"{len(set(self.weight_map.values()))} shards, "
                            f"{len(self.weight_map)} tensors")
                return
        for fname, fmt in (("pytorch_model.bin", "torch"),
                           ("model.safetensors", "safetensors")):
            fpath = os.path.join(path, fname)
            if os.path.isfile(fpath):
                self._format = fmt
                sd = self._load_shard(fname)
                self.weight_map = {k: fname for k in sd}
                return
        raise FileNotFoundError(
            f"No checkpoint found under {path} (looked for sharded index jsons, "
            "pytorch_model.bin, model.safetensors)")

    # ------------------------------------------------------------------ loading
    def _load_shard(self, fname: str) -> Dict[str, Any]:
        if fname not in self._cache:
            fpath = os.path.join(self.path, fname)
            if self._format == "torch":
                import torch
                self._cache[fname] = torch.load(fpath, map_location="cpu",
                                                weights_only=True)
            else:
                from safetensors.numpy import load_file
                self._cache[fname] = load_file(fpath)
        return self._cache[fname]

    def keys(self) -> List[str]:
        return list(self.weight_map)

    def __contains__(self, name: str) -> bool:
        return name in self.weight_map

    def __getitem__(self, name: str) -> np.ndarray:
        shard = self._load_shard(self.weight_map[name])
        t = shard[name]
        if hasattr(t, "detach"):   # torch tensor
            t = t.detach().to("cpu").float().numpy() if t.dtype.is_floating_point \
                else t.detach().cpu().numpy()
        return np.asarray(t)

    def get(self, name: str, default=None):
        return self[name] if name in self else default

    def release_shard(self, fname: str):
        """Free a consumed shard's host memory (streaming discipline)."""
        self._cache.pop(fname, None)

    def shards(self) -> List[str]:
        return sorted(set(self.weight_map.values()))

    def tensors_in_shard(self, fname: str) -> List[str]:
        return [k for k, v in self.weight_map.items() if v == fname]

    def stream(self):
        """Yield ``(name, tensor)`` shard-by-shard, releasing each shard after its
        tensors are consumed — peak host memory is one shard, not the model."""
        for fname in self.shards():
            for name in self.tensors_in_shard(fname):
                yield name, self[name]
            self.release_shard(fname)


# ---------------------------------------------------------------------- MP re-partition
def merge_mp_tensors(tensors: List[np.ndarray], axis: int) -> np.ndarray:
    """Merge model-parallel partitions back into one tensor
    (reference ``MegatronSDLoader.merge_state_dict``)."""
    return np.concatenate([np.asarray(t) for t in tensors], axis=axis)


def split_mp_tensor(tensor: np.ndarray, mp_degree: int, axis: int) -> List[np.ndarray]:
    """Split one tensor into MP partitions
    (reference ``MegatronSDLoader.split_state_dict``)."""
    if not (tensor.shape[axis] % mp_degree == 0):
        raise AssertionError((tensor.shape, mp_degree, axis))
    return list(np.split(np.asarray(tensor), mp_degree, axis=axis))


def merge_qkv_tensors(tensors: List[np.ndarray], axis: int = 0) -> np.ndarray:
    """Merge per-rank fused QKV partitions preserving the q/k/v interleaving
    (reference ``merge_query_key_value:239``): each rank holds [q_i; k_i; v_i] along
    ``axis``; the merged tensor is [q_0..q_n; k_0..k_n; v_0..v_n]."""
    parts = [np.split(np.asarray(t), 3, axis=axis) for t in tensors]
    merged = [np.concatenate([p[j] for p in parts], axis=axis) for j in range(3)]
    return np.concatenate(merged, axis=axis)


def split_qkv_tensor(tensor: np.ndarray, mp_degree: int, axis: int = 0) \
        -> List[np.ndarray]:
    """Inverse of :func:`merge_qkv_tensors` (reference ``split_query_key_value:270``)."""
    q, k, v = np.split(np.asarray(tensor), 3, axis=axis)
    qs = np.split(q, mp_degree, axis=axis)
    ks = np.split(k, mp_degree, axis=axis)
    vs = np.split(v, mp_degree, axis=axis)
    return [np.concatenate([qs[i], ks[i], vs[i]], axis=axis)
            for i in range(mp_degree)]


class SDLoaderFactory:
    """Reference ``SDLoaderFactory:20``: resolve a checkpoint descriptor to a loader."""

    @staticmethod
    def get_sd_loader_json(json_or_dir: str) -> "ShardedStateDict":
        if os.path.isdir(json_or_dir):
            return ShardedStateDict(json_or_dir)
        raise NotImplementedError(
            "Megatron-style descriptor jsons ({'type':..., 'checkpoints': [...]}) "
            "are not supported yet — point at the checkpoint DIRECTORY (HF index "
            "json / single-file layouts); use merge_mp_tensors/split_mp_tensor for "
            "MP re-partitioning")

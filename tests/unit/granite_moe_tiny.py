"""The tiny Granite hybrid WITH experts the CPU tests share: ``granite_tiny``'s
mixers and multipliers, and after every mixer 12 SwiGLU experts of width 32,
the top 3 of a softmax router renormalised, beside a gated shared expert of
width 96 under the same norm (``num_local_experts`` > 0 of
``granite_hybrid_cfg``: granite-4.0-h-small's layer)."""

import importlib.util
import os

from tests.unit import granite_tiny as gt

MODEL = dict(gt.MODEL, num_local_experts=12, num_experts_per_tok=3,
             intermediate_size=32)
PATTERN = "ME*EMEME"


def reference():
    """``benchmarks/chipbench/reference/granite_moe_hybrid.py``, loaded by path."""
    path = os.path.join(gt.REPO, "benchmarks", "chipbench", "reference",
                        "granite_moe_hybrid.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_granite_moe_hybrid", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(**over):
    return gt.config(**{**MODEL, **over})


#: every norm's weight away from one; at its ``init_std`` 0.3 a router's
#: logits spread by ~2, so the softmax over the chosen experts is neither
#: uniform nor one expert's
init = gt.init

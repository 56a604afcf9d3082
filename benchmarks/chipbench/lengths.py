"""The one general generator of request lengths: a traffic file states a
distribution and this draws the FIXED list of ``[prompt, output]`` pairs that
every seed serves (the seed orders each cycle and draws the tokens).

The list is no random draw: value ``i`` of ``count`` is the distribution's
quantile ``(i + 0.5) / count``, so the tail is there in every cycle and no
seed of the benchmark's own decides how heavy it is. Outputs are paired with
prompts in a fixed order drawn from ``pairing_seed``. A pair is then cut so
that ``document + prompt + output`` fits the configuration's serving cap.
"""

import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


def quantiles(spec: dict, count: int) -> List[int]:
    """``count`` whole numbers at the quantiles ``(i + 0.5) / count`` of
    ``spec``: ``{"distribution": "lognormal", "mean", "sigma", "min", "max"}``
    (``mean`` is the distribution's own mean, before the clip)."""
    if spec["distribution"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['distribution']!r}")
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - sigma * sigma / 2.0
    normal = NormalDist()
    out = []
    for i in range(count):
        v = round(math.exp(mu + sigma * normal.inv_cdf((i + 0.5) / count)))
        out.append(int(min(max(v, spec["min"]), spec["max"])))
    return out


def fixed_requests(traffic: dict, cap: int) -> List[Tuple[int, int]]:
    """The cycle of ``(prompt, output)`` pairs a traffic file stands for: its
    ``requests`` list where it gives one (the rehearsals do), else the
    quantiles of its ``lengths``."""
    if "requests" in traffic:
        return [(int(p), int(o)) for p, o in traffic["requests"]]
    spec = traffic["lengths"]
    n = int(spec["count"])
    prompts = quantiles(spec["prompt"], n)
    outputs = quantiles(spec["output"], n)
    order = np.random.default_rng(int(spec["pairing_seed"])).permutation(n)
    room = cap - int(traffic["document_tokens"])
    pairs = []
    for p, j in zip(prompts, order):
        o = min(outputs[int(j)], room - p)
        if o < 2:
            raise ValueError(f"a prompt of {p} tokens leaves no room for two "
                             f"output tokens under {room}")
        pairs.append((p, o))
    return pairs

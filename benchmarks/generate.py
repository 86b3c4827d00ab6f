"""The one general generator of request traffic, read from a data file of
``traffic/`` (``"kind": "requests"``).

Every seed gets the **same set** of request sizes in **another order**, so
that the seed changes which request meets which, not how much work a window
holds. A *block* is ``block`` requests: prompt lengths are the ``block``
mid-quantiles of the prompt distribution and output budgets those of the
output distribution, paired by one permutation fixed in the file
(``pairing_seed``). The list is ``blocks`` such blocks, each shuffled by
``--seed``; token ids are drawn from ``--seed`` too. A closed loop of
``clients`` callers walks the list in order, each taking the next request when
its last reply has arrived.

A length distribution is ``{"dist": "lognormal", "median", "sigma", "min",
"max"}`` (clipped, rounded to whole tokens) or ``{"dist": "fixed", "value"}``.
"""

from __future__ import annotations

import math
import random
import statistics
from typing import Dict, List


def quantile_lengths(spec: dict, n: int) -> List[int]:
    """The ``n`` mid-quantiles of a length distribution, ascending."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    normal = statistics.NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * z)
        out.append(int(round(min(max(x, spec["min"]), spec["max"]))))
    return out


def base_block(traffic: dict) -> List[tuple]:
    """(prompt length, output budget) of one block, the same for every seed."""
    n = traffic["block"]
    prompts = quantile_lengths(traffic["prompt_len"], n)
    outputs = quantile_lengths(traffic["output_len"], n)
    random.Random(traffic["pairing_seed"]).shuffle(outputs)
    return list(zip(prompts, outputs))


def request_list(traffic: dict, seed: int, vocab_size: int) -> List[Dict]:
    """``blocks * block`` requests: ``{"prompt": [ids], "max_new_tokens": n}``."""
    rng = random.Random(seed)
    block = base_block(traffic)
    out = []
    for _ in range(traffic["blocks"]):
        order = list(block)
        rng.shuffle(order)
        for plen, budget in order:
            out.append({"prompt": [rng.randrange(1, vocab_size)
                                   for _ in range(plen)],
                        "max_new_tokens": budget})
    return out


def histogram(values: List[int], edges: List[int]) -> Dict[str, int]:
    """Counts of ``values`` in ``[edge, next edge)``, for the sizing record."""
    out = {}
    for lo, hi in zip(edges, edges[1:] + [None]):
        key = f"{lo}+" if hi is None else f"{lo}-{hi - 1}"
        out[key] = sum(lo <= v and (hi is None or v < hi) for v in values)
    return out

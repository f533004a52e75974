"""One statistic over ALL samples of a list the run observed: ``mean``,
``median`` or a percentile ``pNN``. Percentiles are the nearest-rank
value at or above the share (numpy's ``higher``), so a p99 is a latency
some request really had."""

import numpy as np

from .value import lookup


def read(obs, params):
    samples = lookup(obs, params["key"])
    if samples is None or len(samples) == 0:
        return None
    stat = params["stat"]
    if stat == "mean":
        value = float(np.mean(samples))
    elif stat == "median":
        value = float(np.median(samples))
    elif stat.startswith("p"):
        value = float(np.percentile(samples, float(stat[1:]), method="higher"))
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    return value * params.get("scale", 1.0)

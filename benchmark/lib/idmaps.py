"""The id maps a prepared data set or a model carries (``"u17" <-> 17``),
built at C speed: at 8 M users the template's own loop over the forward
map, which checks that values are unique, took seven times as long as the
rest of a serving cell's set-up. Here they are unique by construction."""

from __future__ import annotations


def id_map(prefix: str, n: int):
    from predictionio_tpu.storage import BiMap

    keys = [f"{prefix}{k}" for k in range(n)]
    return BiMap(dict(zip(keys, range(n))), _inverse=dict(enumerate(keys)))

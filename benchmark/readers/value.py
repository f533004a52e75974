"""A number the run observed as it is: ``key`` (dotted) in the
observations, times ``scale``."""


def lookup(obs, dotted):
    node = obs
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def read(obs, params):
    value = lookup(obs, params["key"])
    if value is None or value != value:  # absent or NaN: nothing to read
        return None
    return value * params.get("scale", 1.0)

"""Change of a counter over the window, alone or over another's.

{"reader": "delta_ratio", "num": SRC, "den": SRC or null, "scale": 1.0}
SRC is {"registry": family, "field": "value"|"count"|"sum"} (the program's
metrics registry, summed over a family's series), {"stats": "a.b"} (a number
of `LLMEngine.stats()`), or {"const": x}.
"""


def _value(snap, src):
    if "registry" in src:
        return snap["registry"].get(src["registry"], {}).get(src.get("field", "value"))
    return snap["stats"].get(src["stats"])


def _delta(obs, src):
    if "const" in src:
        return src["const"]
    after = _value(obs["after"], src)
    if after is None:
        return None
    return after - (_value(obs["before"], src) or 0.0)


def read(spec, obs):
    num = _delta(obs, spec["num"])
    if num is None:
        return None
    scale = spec.get("scale", 1.0)
    if spec.get("den") is None:
        return num * scale
    den = _delta(obs, spec["den"])
    if not den:
        return None
    return num / den * scale

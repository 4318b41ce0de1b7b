"""A quantile over the window's requests of one per-request time.

{"reader": "request_percentile", "field": F, "q": 0.95, "scale": 1000}
F: "gen_late" (submitted - due, the benchmark's clock), "queue_wait" (the
engine's `queue_wait` span), "ttft" (first token - due).
"""
from benchmark.serve import percentile


def read(spec, obs):
    f = spec["field"]
    vals = []
    for r in obs.get("ok", ()):
        if f == "gen_late":
            vals.append(r["submit"] - r["due"])
        elif f == "ttft":
            vals.append(r["first_token"] - r["due"])
        elif r.get(f) is not None:
            vals.append(r[f])
    v = percentile(vals, spec["q"])
    return None if v is None else v * spec.get("scale", 1.0)

"""Each kernel's file gives PATTERNS (substrings of its device-op names) and
classes(obs) -> {class: (flops, bytes)}: the work the traffic required of it."""


def least_seconds(classes, peak, chips=1):
    """Sum over the classes of max(flops / peak flops, bytes / peak bytes/s)
    on `chips` chips, and which bound holds for the largest class."""
    total, bound, most = 0.0, None, -1.0
    for f, b in classes.values():
        tf = f / (peak["bf16_flops"] * chips)
        tb = b / (peak["hbm_bytes_per_s"] * chips)
        t = max(tf, tb)
        total += t
        if t > most:
            most, bound = t, "compute" if tf >= tb else "memory"
    return total, bound

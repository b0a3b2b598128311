"""Device time per ALS sweep of the mode with the most rows, in ms: the
ops under the program's ``als_mode<m>`` scope for that mode, which hold
its factor gather, MTTKRP kernel, unpad and ALS update (the fit is
outside every mode).

Also prints every mode's ms per sweep and the device time under no
``als_mode`` scope.
"""

from bench import named_ops


def read(record):
    per = named_ops.ms_per_sweep(record, lambda op: op.mode)
    if not per or set(per) == {None}:
        return None
    dims = record["window"]["dims"]
    largest = max(range(len(dims)), key=lambda m: dims[m])
    shown = " ".join(f"als_mode{m}={per.get(m, 0.0)!r}" for m in range(len(dims)))
    print(f"[largest_mode_ms] ms per sweep: {shown} none={per.get(None, 0.0)!r} "
          f"largest=als_mode{largest}")
    return per.get(largest, 0.0)

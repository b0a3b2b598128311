"""Share of the roofline reached by the sweep's MTTKRPs, in %.

Numerator: the least time the chip needs for the compulsory bytes and
operations of the sweep's N MTTKRPs (``census.sweep_census``), at the
published peaks of the device (``peaks.json``).  Denominator: device
time of the kernel's events per sweep.  The gather and lane pad that
stage the kernel's operands are not told apart from the rest of the
sweep in today's trace, so they are not in the denominator; the share is
of the kernel alone.  The bound that applies (HBM or compute) is printed.
"""

from bench import census, kernel_events


def read(record):
    w = record["window"]
    kern, _ = kernel_events.split_ns(record["ops"])
    if not kern or not w.get("sweeps"):
        return None
    nbytes, ops = census.sweep_census(w["dims"], w["nnz"], w["rank"])
    t_min, which = census.roofline_time(nbytes, ops, census.peaks(record["device_kind"]))
    print(f"[mttkrp_roofline] bound={which} compulsory_bytes={nbytes} ops={ops} "
          f"least_s_per_sweep={t_min!r}")
    return 100.0 * t_min / (kern * 1e-9 / w["sweeps"])

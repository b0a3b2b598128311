"""Share of the roofline reached by the sweep's staged MTTKRPs, in %.

Numerator: the least time the chip needs for the compulsory bytes and
operations of the sweep's N MTTKRPs (``census.sweep_census``) at the
device's published peaks, as in ``mttkrp_roofline``.  Denominator: the
device time per sweep of every op under the program's ``mttkrp`` scope:
the factor-row gather and lane pad, the kernel, and the unpad slice and
cast.  The bound that applies (HBM or compute) is printed.
"""

from bench import census, program_trace

STAGED = ("mttkrp_gather", "mttkrp_kernel", "mttkrp")


def read(record):
    staged_ms = program_trace.scope_ms(record, STAGED)
    if not staged_ms:
        return None
    w = record["window"]
    nbytes, ops = census.sweep_census(w["dims"], w["nnz"], w["rank"])
    t_min, which = census.roofline_time(nbytes, ops, census.peaks(record["device_kind"]))
    print(f"[mttkrp_staged_roofline] bound={which} least_s_per_sweep={t_min!r} "
          f"staged_ms_per_sweep={staged_ms!r}")
    return 100.0 * t_min / (staged_ms * 1e-3)

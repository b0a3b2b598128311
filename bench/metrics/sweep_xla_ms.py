"""Device time per ALS sweep of every operation that is not the MTTKRP
kernel (factor-row gather, lane pad, Grams and solve, fit), in ms."""

from bench import kernel_events


def read(record):
    kern, other = kernel_events.split_ns(record["ops"])
    sweeps = record["window"].get("sweeps")
    if not kern or not sweeps:
        return None
    return other * 1e-6 / sweeps

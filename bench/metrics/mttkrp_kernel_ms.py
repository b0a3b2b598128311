"""Device time of the Mosaic MTTKRP kernel per ALS sweep, in ms."""

from bench import kernel_events


def read(record):
    kern, _ = kernel_events.split_ns(record["ops"])
    sweeps = record["window"].get("sweeps")
    if not kern or not sweeps:
        return None
    return kern * 1e-6 / sweeps

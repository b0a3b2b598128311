"""Share of the traced window in which no operation ran on the device, in %
(single-job cells): 1 − union of device-operation intervals ÷ window."""

from bench import trace


def read(record):
    t = record["trace"]
    return 100.0 * trace.idle_share(record["ops"], t.window, t.devices)

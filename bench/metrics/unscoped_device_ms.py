"""Device time per ALS sweep of the device ops under none of the
program's scopes, in ms: ops the TPU compiler adds with no ``op_name``
(relayouts, concatenations, copies) and programs outside the sweep,
such as the initialisation's draws.  It guards the scopes after a
refactor.

Also prints every scope's bucket, their sum beside the kernel's events
and device busy time, per sweep (``program_trace.scope_ms``).
"""

from bench import program_trace


def read(record):
    return program_trace.scope_ms(record, (program_trace.UNSCOPED,), label="unscoped_device_ms")

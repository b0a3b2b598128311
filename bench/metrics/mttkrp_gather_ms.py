"""Device time per ALS sweep of the factor-row gather and lane pad that
stage the MTTKRP kernel's operands (ops under the program's
``mttkrp_gather`` scope), in ms.

Also prints every scope's bucket, their sum beside the kernel's events
and device busy time, per sweep (``program_trace.scope_ms``).
"""

from bench import program_trace


def read(record):
    return program_trace.scope_ms(record, ("mttkrp_gather",), label="mttkrp_gather_ms")

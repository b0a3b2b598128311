"""Device time per ALS sweep of the ALS mode updates: Grams, solve and
column normalisation (ops under the program's ``als_update`` scope), in
ms.

Also prints every scope's bucket, their sum beside the kernel's events
and device busy time, per sweep (``program_trace.scope_ms``).
"""

from bench import program_trace


def read(record):
    return program_trace.scope_ms(record, ("als_update",), label="als_update_ms")

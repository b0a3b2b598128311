"""Device time per ALS sweep of the in-graph fit: its Grams, row gathers
and <X, X_hat> (ops under the program's ``als_fit`` scope), in ms.

Also prints every scope's bucket, their sum beside the kernel's events
and device busy time, per sweep (``program_trace.scope_ms``).
"""

from bench import program_trace


def read(record):
    return program_trace.scope_ms(record, ("als_fit",), label="als_fit_ms")

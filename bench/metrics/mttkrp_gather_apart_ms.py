"""Device time per ALS sweep of the MTTKRP gathers of factors too tall
for the gather's factor table, in ms: the ops under the program's
``mttkrp_gather_apart`` scope (nested in ``mttkrp_gather``), each such
factor's own row gather and the update of its slot in the kernel's
operand.  Part of ``mttkrp_gather_ms``.

Also prints how many factor gathers per sweep went apart: the slot
updates (``dynamic_update_slice``) under the scope, one per factor.
"""

from bench import named_ops

SCOPE = "mttkrp_gather_apart"
UPDATE = "dynamic_update_slice"


def read(record):
    per = named_ops.ms_per_sweep(record, lambda op: SCOPE in op.parts)
    if not per or True not in per:
        return None
    updates = sum(SCOPE in op.parts and UPDATE in op.parts for op in named_ops.in_window(record))
    print(f"[mttkrp_gather_apart_ms] apart factor gathers per sweep: "
          f"{updates / record['window']['sweeps']!r}")
    return per[True]

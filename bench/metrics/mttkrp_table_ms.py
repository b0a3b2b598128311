"""Device time per ALS sweep of the MTTKRP gather's factor table, in ms:
the ops under the program's ``mttkrp_table`` scope (nested in
``mttkrp_gather``), which lane-pad the N−1 input factors and concatenate
them into the table the one row gather reads.  Part of
``mttkrp_gather_ms``."""

from bench import named_ops

SCOPE = "mttkrp_table"


def read(record):
    per = named_ops.ms_per_sweep(record, lambda op: SCOPE in op.parts)
    return per.get(True) if per else None

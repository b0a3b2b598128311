"""Device-idle time per ALS sweep inside the program's ``cp_als.run``
spans, in ms: the host's share of each job, on the trace's clock.

Also prints the idle time split by the inner ``cp_als.*`` span that
covers it (``init``, ``block``, ``fit_sync``, ``none``), the window's
whole idle time, and the programs built inside the window (the sum of
the ``cp_als.block`` spans' ``new_program`` stats).
"""

from bench import program_trace, trace


def read(record):
    t = record["trace"]
    sweeps = record["window"].get("sweeps")
    spans = program_trace.in_window(program_trace.newest().spans, t.window)
    if not sweeps or not any(s.name == program_trace.RUN_SPAN for s in spans):
        return None
    split = program_trace.idle_by_span(record["ops"], spans, t.window)
    window_idle = t.window[1] - t.window[0] - trace.busy_ns(record["ops"], t.window, 1)
    shown = " ".join(f"{k}={v * 1e-6 / sweeps!r}" for k, v in split.items())
    print(f"[executor_idle_ms] idle ms per sweep by span: {shown}; "
          f"window idle ms={window_idle * 1e-6!r}; "
          f"programs built inside the window: {program_trace.programs_built(spans)}")
    return sum(split.values()) * 1e-6 / sweeps

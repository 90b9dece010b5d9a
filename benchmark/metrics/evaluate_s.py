"""evaluate_s: seconds per clustering in the driver's `evaluate` stage
(the host float64 modularity of each phase's labels), from the program's
Tracer."""


def read(ctx):
    t = ctx["stage_s"].get("evaluate")
    return None if t is None else t / ctx["n"]

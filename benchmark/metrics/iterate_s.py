"""iterate_s: seconds per clustering in the driver's `iterate` stage (the
on-device phase loops and their one host sync per phase), from the
program's Tracer."""


def read(ctx):
    t = ctx["stage_s"].get("iterate")
    return None if t is None else t / ctx["n"]

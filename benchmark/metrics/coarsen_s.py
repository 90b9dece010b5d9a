"""coarsen_s: seconds per clustering in the driver's `coarsen` stage (the
inter-phase graph rebuild, the device coalesce nested in it), from the
program's Tracer."""


def read(ctx):
    t = ctx["stage_s"].get("coarsen")
    return None if t is None else t / ctx["n"]

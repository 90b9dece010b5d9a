"""partition_s: seconds per clustering in the driver's `partition` stage
(the host DistGraph.build of each phase, nested in `plan`), from the
program's Tracer."""


def read(ctx):
    t = ctx["stage_s"].get("partition")
    return None if t is None else t / ctx["n"]

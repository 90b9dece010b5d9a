"""plan_s: seconds per clustering in the driver's `plan` stage (host
DistGraph and bucket-plan build, with the upload and device re-bin that
run nested in it), from the program's Tracer."""


def read(ctx):
    t = ctx["stage_s"].get("plan")
    return None if t is None else t / ctx["n"]

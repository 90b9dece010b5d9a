"""bucket_s: seconds per clustering in the driver's `bucket` stage (the
host degree bucketing, BucketPlan.build or build_stacked_plans, and its
assembly permutation, nested in `plan`), from the program's Tracer."""


def read(ctx):
    t = ctx["stage_s"].get("bucket")
    return None if t is None else t / ctx["n"]

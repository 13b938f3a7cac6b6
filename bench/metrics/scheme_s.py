"""scheme_s: host seconds of the set-up plan's span ``plan.scheme`` (``PartitionPlan.build_spans``): the distribution scheme (build_scheme)."""


def read(ctx):
    spans = getattr(ctx["plan"], "build_spans", None) or {}
    return spans["plan.scheme"][1] if "plan.scheme" in spans else None

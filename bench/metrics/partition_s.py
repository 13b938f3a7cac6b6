"""partition_s: host seconds of the set-up plan's span ``plan.partition`` (``PartitionPlan.build_spans``): the padded mode partitions (make_mode_partitions)."""


def read(ctx):
    spans = getattr(ctx["plan"], "build_spans", None) or {}
    return spans["plan.partition"][1] if "plan.partition" in spans else None

"""load_imbalance: max over modes of E_max * P / nnz, from the plan's SchemeMetrics."""


def read(ctx):
    per_mode = ctx["plan"].metrics.per_mode
    return max(m.E_max * m.P / m.nnz for m in per_mode)

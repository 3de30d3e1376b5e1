"""Whole-step share of the chip's bf16 peak: the model's own operations
for every output token of the window (the configuration's count,
``token_ops`` of its reference: for qwen3 MF projections, attention at
the cache length served, float LM head; CIM planes not counted) per
second, over the peak."""


def read(run):
    if run.token_ops is None:
        return None
    ops = 0
    for r in run.records:
        p = len(r.prompt)
        ops += sum(run.token_ops(run.sizes, p + j)
                   for j, t in enumerate(r.token_s) if t <= run.window_s)
    if not ops:
        return None
    return 100.0 * ops / run.window_s / run.peaks.bf16_flops

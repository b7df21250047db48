"""The whole served step's share of the chip's peak bfloat16 rate, in
percent: the model operations of the window's prompt and emitted tokens,
counted from the configuration's shapes (``model_flops.py``), over the
traced window's seconds times the peak of its chips (``peaks.json``)."""


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None \
            or "window_flops" not in ctx:
        return None
    return 100 * ctx["window_flops"] / (
        ctx["window"]["seconds"] * ctx["peaks"]["bf16_flops_per_s"]
        * ctx["chips"])

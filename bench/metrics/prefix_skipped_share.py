"""Share of the prompt tokens of the requests admitted in the window
that the scheduler's prefix cache served from resident K/V blocks, in
percent (the program's ``ServeMetrics`` counts, read after the
window)."""


def read(ctx):
    if not ctx.get("prompt_tokens"):
        return None
    return 100 * ctx["prefill_skipped"] / ctx["prompt_tokens"]

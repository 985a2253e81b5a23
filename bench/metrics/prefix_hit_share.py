"""Share of the prompt tokens of the requests sent in the traced window
that prefill skipped because the prefix cache held them."""


def read(ctx):
    sent = ctx.traced_sent()
    prompt = sum(len(r.sent.prompt) for r in sent)
    if not prompt:
        return None
    return 100.0 * sum(r.req.shared_tokens for r in sent) / prompt

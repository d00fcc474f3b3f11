"""Mean number of real rows (requests, not the pow2 bucket) in the window's
decode steps: how full continuous batching keeps the decode batch."""


def read(ctx):
    spans = ctx.get("decode_spans") if ctx["kind"] == "serve" else None
    if not spans:
        return None
    return sum(s["batch"] for s in spans) / len(spans)

"""Mean number of requests prefilled together in the window's prefill calls
(the engine groups requests of equal prompt length)."""


def read(ctx):
    spans = ctx.get("prefill_spans") if ctx["kind"] == "serve" else None
    if not spans:
        return None
    return sum(s["batch"] for s in spans) / len(spans)

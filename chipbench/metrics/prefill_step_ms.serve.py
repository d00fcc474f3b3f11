"""Mean host-clock time of one prefill step in the window: the engine's own
``prefill_s`` over ``prefill_steps``."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["stats"]["prefill_steps"]:
        return None
    return ctx["stats"]["prefill_s"] / ctx["stats"]["prefill_steps"] * 1e3

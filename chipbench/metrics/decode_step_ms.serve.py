"""Mean host-clock time of one decode step in the window: the engine's own
``decode_s`` over ``decode_steps``, each step timed around its
``block_until_ready``."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["stats"]["decode_steps"]:
        return None
    return ctx["stats"]["decode_s"] / ctx["stats"]["decode_steps"] * 1e3

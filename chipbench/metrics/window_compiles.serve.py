"""Backend compiles inside the serving window; set-up warms every program
the window runs, so this should read 0."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return ctx["window_compiles"]

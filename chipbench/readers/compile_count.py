"""Reader ``compile_count``: backend compile events jax reported between
the window's start and end (a persistent-cache hit passes through the same
event, so cold and cached runs count the same thing). Expected 0."""


def read(ctx: dict):
    return ctx["compiles"]["in_window"]

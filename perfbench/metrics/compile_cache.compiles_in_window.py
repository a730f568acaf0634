"""Programs jax built inside the window (its
``backend_compile_duration`` event, from the cache or not). Should be 0:
every shape is warmed up before."""


def read(ctx):
    return ctx.compiles_in_window()

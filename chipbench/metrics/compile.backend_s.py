"""Seconds JAX spent in set-up compiling programs or reading them back from
the persistent compilation cache (its backend-compile events)."""


def read(ctx):
    return ctx.run.setup_compile_s

"""Build (core/build.py): rows inserted per second by
``NavixDB.create_index``, host clock around the call ending in
``block_until_ready``."""


def read(ctx):
    return ctx.cfg["n"] / ctx.build_s if ctx.build_s > 0 else None

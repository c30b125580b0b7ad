"""Executor: jitted launches the runner made per thousand uploads (its
own ``launches`` counter over the traced run's uploads)."""


def read(ctx):
    launches, uploads = ctx.stats.get("launches"), ctx.stats.get("uploads")
    if not launches or not uploads:
        return None
    return 1000.0 * launches / uploads

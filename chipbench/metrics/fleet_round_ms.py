"""Client plane, fleet-wide round: device time per execution of the
program that trains every client at once (``ClientPlane.train_all``, a
jitted lambda: ``jit__lambda`` in the trace), the fleet init of a
CSMAAFL run and each FedAvg round."""


def read(ctx):
    secs, n = ctx.module_time_s("jit__lambda")
    return 1e3 * secs / n if n else None

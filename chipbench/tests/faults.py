"""Faults planted in the program under a whole benchmark run."""
import numpy as np


def state_unchanged(monkeypatch):
    """Local SGD returns the row it was given: a zero-gradient loss."""
    from repro.models import cnn

    orig = cnn.loss_fn
    monkeypatch.setattr(cnn, "loss_fn", lambda p, b: 0.0 * orig(p, b))


def half_batch(monkeypatch):
    """Each minibatch's loss is the mean over its first half only."""
    from repro.models import cnn

    orig = cnn.loss_fn

    def loss(p, b):
        k = max(1, b["labels"].shape[0] // 2)
        return orig(p, {"images": b["images"][:k], "labels": b["labels"][:k]})

    monkeypatch.setattr(cnn, "loss_fn", loss)


def altered_beta(monkeypatch):
    """One upload's eq. (11) coefficient is off by 0.01 where the trace
    compiler produces it."""
    from repro.core import event_trace

    orig = event_trace.compile_afl_trace

    def compile_afl_trace(*a, **kw):
        tr = orig(*a, **kw)
        tr.betas = tr.betas.copy()
        k = len(tr.betas) // 2
        tr.betas[k] = min(1.0, tr.betas[k] + 0.01)
        return tr

    monkeypatch.setattr(event_trace, "compile_afl_trace", compile_afl_trace)


def altered_uploader(monkeypatch):
    """One upload is credited to the next client where the scheduler
    produces the timeline."""
    import dataclasses

    from repro.core import scheduler

    orig = scheduler.AFLScheduler.trace

    def trace(self, max_iterations):
        evs = orig(self, max_iterations)
        k = len(evs) // 2
        evs[k] = dataclasses.replace(evs[k],
                                     cid=(evs[k].cid + 1) % len(self.fleet))
        return evs

    monkeypatch.setattr(scheduler.AFLScheduler, "trace", trace)


def altered_alpha(monkeypatch):
    """FedAvg's aggregation weights move one client's share onto its
    neighbour where they are produced."""
    from repro.core import aggregation

    orig = aggregation.sfl_alpha

    def sfl_alpha(samples):
        a = np.array(orig(samples))
        a[1] += a[0]
        a[0] = 0.0
        return a

    monkeypatch.setattr(aggregation, "sfl_alpha", sfl_alpha)


def _blend_fault(monkeypatch, blend):
    """Every eq. (3) blend of the compiled scan goes through ``blend(orig,
    g, row, coefs)`` in place of the engine's own."""
    from repro.core import event_trace

    orig = event_trace.make_scan_step

    class Engine:
        def __init__(self, base):
            self._base = base

        def __getattr__(self, name):
            return getattr(self._base, name)

        def blend_row_expr(self, g, row, coefs):
            return blend(self._base.blend_row_expr, g, row, coefs)

    def make_scan_step(base_engine, *a, **kw):
        return orig(Engine(base_engine), *a, **kw)

    monkeypatch.setattr(event_trace, "make_scan_step", make_scan_step)


def blend_bf16(monkeypatch):
    """The blend's operands, coefficients and sum in bfloat16."""
    import jax.numpy as jnp

    def blend(_orig, g, row, cf):
        bf = jnp.bfloat16
        return (cf[0].astype(bf) * g.astype(bf)
                + cf[1].astype(bf) * row.astype(bf)).astype(g.dtype)

    _blend_fault(monkeypatch, blend)


def blend_coef(monkeypatch):
    """Every blend takes a coefficient 0.01 off: beta - 0.01 on the
    global, 1 - beta + 0.01 on the uploaded row."""
    import jax.numpy as jnp

    def blend(orig, g, row, cf):
        return orig(g, row, cf + jnp.asarray([-0.01, 0.01], cf.dtype))

    _blend_fault(monkeypatch, blend)

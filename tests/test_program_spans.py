"""The program's own host spans (docs/DESIGN.md §7): a ``jax.profiler``
trace of a tiny compiled CSMAAFL run and a tiny FedAvg run holds every
span the runtimes record, each inside the caller's span, with the run's
seed (and a segment's bounds) as arguments.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.afl import run_afl
from repro.core.agg_engine import AggEngine
from repro.core.client_plane import ClientPlane
from repro.core.scheduler import ClientSpec
from repro.core.sfl import run_fedavg

D = 8
SEED = 7


def _plane(local_steps=(2, 1, 1, 1)):
    fleet = [ClientSpec(cid=m, tau_compute=1.0 + 0.5 * m, num_samples=50,
                        local_steps=k) for m, k in enumerate(local_steps)]
    w0 = jnp.zeros(D, jnp.float32)

    def batch_fn(cid, num_steps, seed):
        r = np.random.default_rng(seed * 131 + cid)
        return r.normal(size=(num_steps * 2, D)).astype(np.float32)

    def step(flat, target):
        return flat - 0.25 * (flat - target)

    return w0, fleet, ClientPlane(AggEngine(w0), fleet, step, batch_fn)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return spans


@pytest.fixture(scope="module")
def traced_spans(tmp_path_factory):
    w0, fleet, plane = _plane()

    def eval_fn(p):
        return {"s": float(jnp.sum(p))}

    def runs():
        run_afl(w0, fleet, None, client_plane=plane, compiled_loop=True,
                algorithm="csmaafl", iterations=12, tau_u=0.1, tau_d=0.1,
                eval_fn=eval_fn, eval_every=8, seed=SEED)
        run_fedavg(w0, fleet, None, client_plane=plane, rounds=2,
                   tau_u=0.1, tau_d=0.1, eval_fn=eval_fn, seed=SEED)

    runs()                       # compile outside the trace
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.run"):
            runs()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(trace_dir)


SPANS = ("afl.compile_trace", "afl.stage_events", "afl.segment",
         "afl.segment_inputs", "afl.eval", "plane.stage_fleet",
         "fedavg.round")


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("name", SPANS)
def test_every_span_is_recorded_inside_the_run(traced_spans, name):
    run = [s for s in traced_spans if s[0] == "bench.run"]
    got = [s for s in traced_spans if s[0] == name]
    assert len(run) == 1 and got, name
    assert all(_inside(s, run[0]) for s in got)
    for s in got:
        if name != "plane.stage_fleet":
            assert s[3]["seed"] == SEED, s
        else:
            assert s[3]["clients"] == 4


def test_spans_nest_and_carry_their_segment(traced_spans):
    by = {n: [s for s in traced_spans if s[0] == n] for n in SPANS}
    # the eval at upload 8 cuts the 12 uploads into two segments
    assert [(s[3]["s0"], s[3]["s1"]) for s in by["afl.segment"]] == \
        [(0, 8), (8, 12)]
    assert all(s[3]["bucket"] == 4 for s in by["afl.segment"])
    for inp in by["afl.segment_inputs"]:
        seg = [s for s in by["afl.segment"] if _inside(inp, s)]
        assert len(seg) == 1
        assert (inp[3]["s0"], inp[3]["s1"]) == (seg[0][3]["s0"],
                                               seg[0][3]["s1"])
    # the CSMAAFL fleet init stages once outside any round; each FedAvg
    # round stages once inside it
    rounds = by["fedavg.round"]
    assert [s[3]["round"] for s in rounds] == [1, 2]
    inside = [st for st in by["plane.stage_fleet"]
              if any(_inside(st, r) for r in rounds)]
    assert len(inside) == 2 and len(by["plane.stage_fleet"]) == 3
    # evals at uploads 0 and 8 of the compiled run
    assert sorted(s[3]["upload"] for s in by["afl.eval"]) == [0, 8]

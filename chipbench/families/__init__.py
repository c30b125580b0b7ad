"""Model families: what the benchmark needs to know of one kind of
client model, found by name.

A configuration names its family (``"family": "paper_cnn"``); the
module is ``families/<family>.py``, loaded by ``spec.family``.  The
federated replay (``reference.py``), the check (``check.py``), the
counts (``flops.py``) and the entry (``entries/api_run.py``) are shared
by every family and ask it for these, so a new kind of client model is a
new file here and never an edit.  ``config`` is the configuration's
whole file, ``model`` its ``model`` block.

Reference side (imports nothing of ``repro``: the reference is built
from the published description alone, and takes nothing the program
made):

* ``dataset(config, seed) -> (data, keys)`` — the data the steps read,
  rebuilt from the data seed, as a pytree of host arrays (the replay
  puts it on the device once and passes it to every program as an
  argument), and one label or key per sample, which the partitions deal
  out to the clients.
* ``local_steps(config, k) -> int`` — the SGD steps of K local
  iterations.
* ``batches(config, shard, cid, k, seed)`` — client ``cid``'s minibatches
  for K local iterations, drawn from its ``shard`` (sorted sample
  indices) as the program draws them: a pytree whose leaves lead with
  the step axis (``local_steps(config, k)`` long), then the batch axis.
  The replay pads the steps to the cell's length with masked steps, and
  its ``half_batch`` fault cuts the batch axis in half.
* ``init_params(model, seed)`` — the weights a run starts from, a pytree
  of float32 leaves; the entry jits it as ``bench_init``.
* ``loss(params, batch, data)`` — the plain float32 mean loss of one
  step's batch (each leaf of ``batches`` without its step axis).

Counts (from the configuration's shapes, never from the program or
XLA): ``param_count(model)`` and ``forward_flops_per_sample(model)``,
2 x the multiply-adds of one sample's forward pass.

Program side (imports ``repro`` inside the function):

* ``task(config, iid, seed)`` — the task ``entries/api_run.py`` hands to
  ``repro.api.run``, its data made from ``seed``.
* ``samples_per_iteration(config)`` — the samples one local iteration
  trains, in the unit ``forward_flops_per_sample`` counts.
"""

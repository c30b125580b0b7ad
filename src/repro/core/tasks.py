"""Task harnesses: bind a model family + dataset to the FL loops.

``CNNTask`` is the paper's §IV setup (CNN on (Fashion-)MNIST-like data);
``LMTask`` federates a (reduced) assigned transformer architecture over
synthetic non-IID token streams — the modern deployment of the algorithm
used by the examples and integration tests; ``LoRALMTask`` federates
LoRA adapters over a frozen language model, each client's row being its
adapters.

``CNNTask`` and ``LMTask`` expose two local-training surfaces
(``LoRALMTask`` only the second):

* ``local_train_fn`` — the per-minibatch reference path (one jitted SGD
  dispatch per minibatch).  The CNN variant stages the WHOLE training
  set as device arrays at construction and gathers minibatches on device
  by index, so even the reference path never re-uploads image tensors
  host→device inside the training loop.
* ``client_plane(fleet)`` — the fused fleet plane (docs/DESIGN.md §4):
  one SGD step on the engine's per-leaf tree in the storage dtype,
  minibatches staged per round, local SGD scanned and vmapped by
  ``core.client_plane.ClientPlane``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.configs.base import ModelConfig
from repro.configs.paper_cnn import CNNConfig, MNIST_CNN
from repro.data import federated as fd
from repro.data.mnist_like import make_dataset
from repro.data.synthetic import TokenStream, topic_corpus
from repro.models import cnn as cnn_mod
from repro.models import lora
from repro.models import transformer as tmod


# ---------------------------------------------------------------------------
# CNN task (paper §IV)
# ---------------------------------------------------------------------------
class CNNTask:
    def __init__(self, *, variant: str = "digits", iid: bool = True,
                 num_clients: int = 100, train_n: int = 60000,
                 test_n: int = 10000, batch_size: int = 5, lr: float = 0.01,
                 local_batches_per_step: int = 8,
                 cnn_cfg: Optional[CNNConfig] = None, seed: int = 0):
        self.cfg = cnn_cfg or MNIST_CNN
        self.lr = lr
        self.batch_size = batch_size
        # per-client overrides (ClientSpec.batch_size) — populated by
        # ``client_plane`` when the fleet declares heterogeneous sizes,
        # so the per-minibatch reference path draws the SAME batches
        self._batch_size_by_cid: Dict[int, int] = {}
        self.local_batches = local_batches_per_step
        ds = make_dataset(variant, train_n=train_n, test_n=test_n, seed=seed)
        # the raw (host) arrays stay around so scenario sweeps can re-
        # partition the SAME dataset per scenario (``scenario_clients``)
        self._train_x_np, self._train_y_np = ds.train_x, ds.train_y
        parts = fd.partition("iid" if iid else "label", ds.train_y,
                             num_clients, seed=seed,
                             **({} if iid else {"classes_per_client": 2}))
        self.clients = fd.make_clients(ds.train_x, ds.train_y, parts)
        # the WHOLE training set lives on device once; minibatches are
        # gathered by index inside the jitted step (no per-minibatch
        # host→device image upload on ANY training path).  The data
        # arrays are ARGUMENTS of every program that reads them — closed
        # over, XLA would embed them as constants (188 MB of images in
        # each compiled variant)
        self._train_x = jnp.asarray(ds.train_x)
        self._train_y = jnp.asarray(ds.train_y)
        self.test_x = jnp.asarray(ds.test_x)
        self.test_y = jnp.asarray(ds.test_y)

        @jax.jit
        def _sgd_step(params, idx, train_x, train_y):
            batch = {"images": train_x[idx], "labels": train_y[idx]}
            loss, grads = jax.value_and_grad(cnn_mod.loss_fn)(params, batch)
            new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new, loss

        self._sgd_step = _sgd_step
        self._eval = jax.jit(cnn_mod.accuracy)

    def init_params(self, seed: int = 0):
        return cnn_mod.init_params(self.cfg, jax.random.PRNGKey(seed))

    def num_samples(self) -> List[int]:
        return [c.num_samples for c in self.clients]

    def scenario_clients(self, partitioner: str, seed: int = 0,
                         **kw) -> List[fd.ClientDataset]:
        """Re-partition the task's dataset through the partitioner
        registry (``data.federated.PARTITIONERS``) — the sweep plane
        builds one shard set per (scenario, seed) over the SAME staged
        dataset, so R runs cost one device copy of the images."""
        parts = fd.partition(partitioner, self._train_y_np,
                             len(self.clients), seed=seed, **kw)
        return fd.make_clients(self._train_x_np, self._train_y_np, parts)

    def _batch_indices_fn(self, clients):
        """``batch_fn`` bound to an explicit shard set (scenario sweeps
        pass per-scenario partitions; the default path uses
        ``self.clients``)."""

        def batch_fn(cid: int, num_steps: int, seed: int) -> np.ndarray:
            client = clients[cid]
            bs = self._batch_size_by_cid.get(cid, self.batch_size)
            local = client.batch_indices(
                bs, num_steps * self.local_batches, seed)
            return client.indices[local].astype(np.int32)

        return batch_fn

    def _global_batch_indices(self, cid: int, num_steps: int, seed: int
                              ) -> np.ndarray:
        """(num_batches, B_cid) indices into the staged full training
        set; B_cid honors a per-client ``ClientSpec.batch_size``."""
        return self._batch_indices_fn(self.clients)(cid, num_steps, seed)

    def local_train_fn(self, params, cid: int, num_steps: int, seed: int):
        """K "local iterations"; each = ``local_batches`` SGD minibatches
        (so K scales client compute as in §III-C).  Per-minibatch
        reference path: one dispatch per minibatch, but only the (tiny)
        index array crosses host→device."""
        idx = self._global_batch_indices(cid, num_steps, seed)
        for row in idx:
            params, _ = self._sgd_step(params, row, self._train_x,
                                       self._train_y)
        return params

    def client_plane(self, fleet, *, sharded: bool = False, clients=None,
                     **plane_kw):
        """Fused fleet plane: grad against the engine's per-leaf tree
        (the plane's scan carry, in the storage dtype); batches staged as
        index arrays (the image gather happens on device inside scan).
        ``sharded=True`` builds the fleet-mesh plane (DESIGN.md §6);
        ``clients`` overrides the shard set (scenario sweeps pass the
        per-scenario partition from ``scenario_clients``).

        Fleets declaring per-client ``ClientSpec.batch_size`` get the
        plane's sample-axis padding (§4): each scan step then receives
        ``{"batch": (B_pad,) idx, "sample_valid": (B_pad,) bool}`` and
        the loss is the masked per-sample mean — identical to the
        per-minibatch reference path's plain mean over the client's true
        B_m samples (which ``local_train_fn`` also honors once the plane
        has registered the per-client sizes)."""
        from repro.core.agg_engine import engine_for
        from repro.core.client_plane import build_plane

        # rebuilt per fleet — stale per-cid sizes from a previous fleet
        # must not leak into this one's batch draws
        self._batch_size_by_cid = {
            c.cid: int(c.batch_size) for c in fleet
            if getattr(c, "batch_size", None) is not None}

        template = jax.eval_shape(
            lambda: cnn_mod.init_params(self.cfg, jax.random.PRNGKey(0)))
        engine = engine_for(template)
        cast = engine.cast_expr
        lr = self.lr

        def step_fn(train_x, train_y, tree, batch):
            if isinstance(batch, dict):      # ragged fleet: masked mean
                idx, mask = batch["batch"], batch["sample_valid"]
            else:
                idx, mask = batch, None
            images, labels = train_x[idx], train_y[idx]

            def loss_tree(t):
                params = cast(t)
                if mask is None:
                    return cnn_mod.loss_fn(
                        params, {"images": images, "labels": labels})
                logp = cnn_mod.forward(params, images)
                nll = -jnp.take_along_axis(
                    logp, labels[:, None], axis=-1)[:, 0]
                m = mask.astype(jnp.float32)
                return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)

            grads = jax.grad(loss_tree)(tree)
            return jax.tree.map(lambda p, g: p - lr * g, tree, grads)

        step = Partial(step_fn, self._train_x, self._train_y)
        # advertise the {"batch", "sample_valid"} staging contract so the
        # plane accepts fleets with declared per-client batch sizes
        step.supports_sample_mask = True

        batch_fn = (self._global_batch_indices if clients is None
                    else self._batch_indices_fn(clients))
        return build_plane(engine, fleet, step, batch_fn,
                           sharded=sharded, **plane_kw)

    def eval_fn(self, params) -> Dict[str, float]:
        return {"accuracy": float(self._eval(params, self.test_x,
                                              self.test_y))}

    def eval_flat_fn(self, engine):
        """Traceable eval against the FLAT parameter vector — the sweep
        plane vmaps it across a run group's stacked (R, n) globals so a
        grid's eval points are one launch each (DESIGN.md §8)."""
        unflatten = engine.unflatten_expr

        def eval_flat(test_x, test_y, g_flat):
            return {"accuracy": cnn_mod.accuracy(unflatten(g_flat),
                                                 test_x, test_y)}

        return Partial(eval_flat, self.test_x, self.test_y)


# ---------------------------------------------------------------------------
# LM task (assigned architectures, reduced configs on CPU)
# ---------------------------------------------------------------------------
class LMTask:
    def __init__(self, cfg: ModelConfig, *, num_clients: int = 8,
                 batch_size: int = 4, seq_len: int = 64, lr: float = 5e-3,
                 seed: int = 0):
        self.cfg = cfg
        self.lr = lr
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.streams = [TokenStream(cfg.vocab_size, cid=c, seed=seed)
                        for c in range(num_clients)]
        self.eval_stream = TokenStream(cfg.vocab_size, cid=10_007, seed=seed,
                                       topics_per_client=16)
        self._eval_batch = self._to_model_batch(
            self.eval_stream.sample_batch(batch_size, seq_len))

        @jax.jit
        def _sgd_step(params, batch):
            (loss, _), grads = jax.value_and_grad(
                tmod.loss_fn, has_aux=True)(params, cfg, batch)
            new = jax.tree.map(
                lambda p, g: (p.astype(jnp.float32) -
                              lr * g.astype(jnp.float32)).astype(p.dtype),
                params, grads)
            return new, loss

        self._sgd_step = _sgd_step

        @jax.jit
        def _eval(params):
            loss, _ = tmod.loss_fn(params, cfg, self._eval_batch)
            return loss

        self._eval = _eval

    def _modality_stubs(self, B: int) -> Dict[str, jnp.ndarray]:
        """Zero stubs for the non-token modalities (single source of
        their shapes — used by the per-minibatch path and rebuilt inside
        the plane's jitted step so they never cross host→device)."""
        out: Dict[str, jnp.ndarray] = {}
        if self.cfg.num_patches:
            out["patch_embeds"] = jnp.zeros(
                (B, self.cfg.num_patches, self.cfg.vision_embed_dim),
                jnp.float32)
        if self.cfg.enc_layers:
            out["frame_embeds"] = jnp.zeros(
                (B, self.seq_len // self.cfg.enc_seq_divisor,
                 self.cfg.d_model), jnp.float32)
        return out

    def _to_model_batch(self, b: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        out = {"tokens": jnp.asarray(b["tokens"]),
               "labels": jnp.asarray(b["labels"])}
        out.update(self._modality_stubs(out["tokens"].shape[0]))
        return out

    def init_params(self, seed: int = 0):
        return tmod.init_params(self.cfg, jax.random.PRNGKey(seed))

    def num_samples(self) -> List[int]:
        return [1000] * len(self.streams)

    def local_train_fn(self, params, cid: int, num_steps: int, seed: int):
        for _ in range(num_steps):
            b = self._to_model_batch(
                self.streams[cid].sample_batch(self.batch_size, self.seq_len))
            params, _ = self._sgd_step(params, b)
        return params

    def client_plane(self, fleet, *, sharded: bool = False, **plane_kw):
        """Fused fleet plane for the LM task.  Each round's token batches
        are pre-sampled and staged as one (KB, B, S) array; the zero
        modality stubs (patch/frame embeds) are rebuilt inside the jitted
        step so they never cross host→device.  Streams advance exactly as
        the per-minibatch path does (same draws per call), so plane-on
        and plane-off consume identical token sequences.
        ``sharded=True`` builds the fleet-mesh plane (DESIGN.md §6)."""
        from repro.core.agg_engine import engine_for
        from repro.core.client_plane import build_plane

        cfg, lr, seq_len = self.cfg, self.lr, self.seq_len
        template = jax.eval_shape(
            lambda: tmod.init_params(cfg, jax.random.PRNGKey(0)))
        engine = engine_for(template)
        cast = engine.cast_expr

        def step_fn(tree, batch):
            full = dict(batch)
            full.update(self._modality_stubs(batch["tokens"].shape[0]))

            def loss_tree(t):
                loss, _ = tmod.loss_fn(cast(t), cfg, full)
                return loss

            grads = jax.grad(loss_tree)(tree)
            return jax.tree.map(
                lambda p, g: (p.astype(jnp.float32)
                              - lr * g.astype(jnp.float32)).astype(p.dtype),
                tree, grads)

        def batch_fn(cid, num_steps, seed):
            bs = [self.streams[cid].sample_batch(self.batch_size, seq_len)
                  for _ in range(num_steps)]
            return {"tokens": np.stack([b["tokens"] for b in bs]),
                    "labels": np.stack([b["labels"] for b in bs])}

        return build_plane(engine, fleet, step_fn, batch_fn,
                           sharded=sharded, **plane_kw)

    def eval_fn(self, params) -> Dict[str, float]:
        return {"loss": float(self._eval(params))}


# ---------------------------------------------------------------------------
# LoRA LM task (adapters over a frozen base)
# ---------------------------------------------------------------------------
class LoRALMTask:
    """Federated LoRA fine-tuning of a language model (``models/lora.py``):
    the engine's tree, and so each client's row, is the adapters alone.

    The frozen base (``lora.frozen_base``) and the token corpus
    (``data.synthetic.topic_corpus``: ``train_seqs`` training sequences,
    then ``eval_seqs`` held out) are drawn from ``seed`` and live on the
    device once; both are arguments of every plane program and of the
    eval (a ``Partial``, as ``CNNTask`` binds its images), never
    constants.  The training sequences are partitioned iid or by topic;
    a client's minibatches are index rows into the corpus drawn from
    ``(cid, seed)`` alone (``ClientDataset.batch_indices``), so a run's
    batches do not depend on what ran before it.  Local training is
    plain SGD on the adapters; the loss is the mean next-token cross
    entropy, the vocabulary projection chunked over the sequence."""

    def __init__(self, cfg: ModelConfig, *, rank: int = 16,
                 alpha: float = 32.0, iid: bool = True,
                 num_clients: int = 8, batch_size: int = 2,
                 seq_len: int = 1024, local_batches_per_step: int = 1,
                 train_seqs: int = 128, eval_seqs: int = 4,
                 num_topics: int = 16, lr: float = 2.0, seed: int = 0):
        self.cfg = cfg = dataclasses.replace(cfg, scan_layers=False)
        self.rank, self.scale = rank, alpha / rank
        self.lr = lr
        self.batch_size = batch_size
        self.local_batches = local_batches_per_step
        self.base = lora.frozen_base(cfg, seed)
        tokens, topics = topic_corpus(cfg.vocab_size, train_seqs + eval_seqs,
                                      seq_len, num_topics=num_topics,
                                      seed=seed)
        self.tokens = jnp.asarray(tokens)
        parts = fd.partition("iid" if iid else "label", topics[:train_seqs],
                             num_clients, seed=seed,
                             **({} if iid else {"classes_per_client": 2}))
        self.clients = fd.make_clients(tokens[:train_seqs],
                                       topics[:train_seqs], parts)
        self._eval_rows = jnp.arange(train_seqs, train_seqs + eval_seqs)
        scale = self.scale

        # closes over no array: a cache that keeps a program's function
        # keeps no base with it
        def loss(base, tokens, adapters, rows):
            seqs = tokens[rows]
            batch = {"tokens": seqs[:, :-1], "labels": seqs[:, 1:]}
            value, _ = tmod.loss_fn(lora.attach(base, adapters, scale), cfg,
                                    batch, chunked=True)
            return value

        self._loss = loss
        self._eval = jax.jit(loss)

    def init_params(self, seed: int = 0):
        return lora.init_adapters(self.cfg, self.rank,
                                  jax.random.PRNGKey(seed), self.base)

    def num_samples(self) -> List[int]:
        return [c.num_samples for c in self.clients]

    def _batch_rows(self, cid: int, num_steps: int, seed: int
                    ) -> np.ndarray:
        """(num_steps · local batches, batch_size) corpus rows."""
        client = self.clients[cid]
        local = client.batch_indices(self.batch_size,
                                     num_steps * self.local_batches, seed)
        return client.indices[local].astype(np.int32)

    def client_plane(self, fleet, *, sharded: bool = False, **plane_kw):
        """Fused fleet plane over the adapters; the base and the corpus
        ride in the step's ``Partial``."""
        from repro.core.agg_engine import engine_for
        from repro.core.client_plane import build_plane

        engine = engine_for(jax.eval_shape(self.init_params))
        cast, loss, lr = engine.cast_expr, self._loss, self.lr

        def step_fn(base, tokens, tree, rows):
            """One SGD step on the adapters (the engine's tree)."""
            grads = jax.grad(
                lambda t: loss(base, tokens, cast(t), rows))(tree)
            return jax.tree.map(lambda p, g: p - lr * g, tree, grads)

        return build_plane(engine, fleet,
                           Partial(step_fn, self.base, self.tokens),
                           self._batch_rows, sharded=sharded, **plane_kw)

    def eval_fn(self, params) -> Dict[str, float]:
        """Mean loss on the held-out sequences."""
        return {"loss": float(self._eval(self.base, self.tokens, params,
                                         self._eval_rows))}

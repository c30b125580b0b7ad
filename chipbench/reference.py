"""Plain float32 reference of the benchmarked federated runs, shared by
every model family.

It imports nothing of the program under test (``repro``) and takes
nothing the program made.  From a configuration, its model family
(``families/``), a traffic mix and a seed it rebuilds every input itself
and replays the run the paper describes, one step at a time:

* the family's training data, rebuilt from the data seed;
* the IID and label-shard partitions of the family's per-sample keys,
  the heterogeneous fleet (compute time log-uniform in [tau, a*tau],
  adaptive K) and each client's seeded minibatches, drawn by the family;
* the CSMAAFL channel scheduler (one upload at a time, the older model
  first) and the eq. (11) coefficient with its staleness EMA run as the
  plain recurrence;
* local training as plain SGD on the family's loss;
* eq. (3), w <- beta*w + (1 - beta)*w_m, one upload at a time, each
  uploader retraining from the new global; FedAvg rounds as the
  alpha-weighted sum of every client's locally trained model.

A fleet-wide round (FedAvg's, and CSMAAFL's first training of every
client from w0) is the one-client training vmapped over the clients,
and a weighted sum adds one product per client in client order: the
order in which the program's path does them, so that a sound run on a
TPU matches the reference bit for bit.

Every matmul and convolution runs at the precision it is given
("highest" by default: float32 on a TPU); a lower ``precision`` is the
correctness check's control.  ``fault`` plants one of the
faults the correctness check has to catch, for the calibration and the
tests: ``half_batch`` trains on the first half of each minibatch's batch
axis and takes the mean over it; ``alter_answer`` hands one upload to
another client with a beta 0.01 off (FedAvg: one client's weight to
another); ``blend_bf16`` computes every eq. (3) blend in bfloat16, and
``blend_coef`` every one with a coefficient 0.01 off.  None of them
touches the family's code.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Any, Callable, Dict, List, Optional

import numpy as np

FAULTS = (None, "half_batch", "alter_answer", "blend_bf16", "blend_coef")


# ---------------------------------------------------------------------------
# Partitions
# ---------------------------------------------------------------------------
def partition(kind: str, labels: np.ndarray, num_clients: int, seed: int,
              classes_per_client: int = 2) -> List[np.ndarray]:
    """Each client's sorted sample indices: ``iid`` deals a random
    permutation out equally; ``label`` deals ``classes_per_client``
    label-sorted shards to each client."""
    rng = np.random.default_rng(seed)
    if kind == "iid":
        idx = rng.permutation(len(labels))
        return [np.sort(s) for s in np.array_split(idx, num_clients)]
    if kind == "label":
        order = np.argsort(labels, kind="stable")
        shards = np.array_split(order, num_clients * classes_per_client)
        ids = rng.permutation(num_clients * classes_per_client)
        return [np.sort(np.concatenate(
            [shards[s] for s in ids[c * classes_per_client:
                                    (c + 1) * classes_per_client]]))
            for c in range(num_clients)]
    raise ValueError(f"unknown partition {kind!r}")


# ---------------------------------------------------------------------------
# Fleet, scheduler and coefficients
# ---------------------------------------------------------------------------
def fleet_steps(num_clients: int, *, tau: float, hetero_a: float,
                seed: int, adaptive: bool, min_steps: int, max_steps: int,
                base_local_steps: int):
    """(compute time per local iteration, K) of every client."""
    rng = np.random.default_rng(seed)
    taus = np.exp(rng.uniform(np.log(tau), np.log(hetero_a * tau),
                              num_clients))
    taus[rng.integers(num_clients)] = tau
    taus[rng.integers(num_clients)] = hetero_a * tau
    median = float(np.median(taus))
    ks = []
    for c in range(num_clients):
        k = base_local_steps
        if adaptive:
            k = int(np.clip(round(base_local_steps * median / taus[c]),
                            min_steps, max_steps))
        ks.append(k)
    return taus, ks


def schedule(taus, ks, *, tau_u: float, tau_d: float, iterations: int):
    """The upload timeline: one (uploader, j, i) per upload.  A client
    computes K·tau, waits for the single upload channel, uploads in
    tau_u; among those waiting the one whose model is older (earlier
    previous slot) goes first; the fresh model comes back in tau_d."""
    M = len(taus)
    heap = [(tau_d + ks[c] * taus[c], c) for c in range(M)]
    heapq.heapify(heap)
    got = [0] * M
    last = [-1] * M
    waiting = []           # (t_ready, cid, last slot)
    t_free = 0.0
    out = []
    for j in range(1, iterations + 1):
        if not waiting:
            t, c = heapq.heappop(heap)
            waiting.append((t, c, last[c]))
        t_serve = max(t_free, min(w[0] for w in waiting))
        while heap and heap[0][0] <= t_serve:
            t, c = heapq.heappop(heap)
            waiting.append((t, c, last[c]))
        ready = [w for w in waiting if w[0] <= t_serve]
        pick = min(ready, key=lambda w: (w[2], w[0], w[1]))
        waiting.remove(pick)
        c = pick[1]
        out.append((c, j, got[c]))
        got[c] = j
        last[c] = j
        t_free = t_serve + tau_u
        heapq.heappush(heap, (t_free + tau_d + ks[c] * taus[c], c))
    return out


def csmaafl_betas(events, *, gamma: float, mu_momentum: float):
    """beta_j of eq. (11): 1 - min(1, mu / (gamma·j·(j - i))), mu the
    moving average of the staleness max(j - i, 1), seeded by the first."""
    betas = []
    mu = None
    for _c, j, i in events:
        s = max(float(j - i), 1.0)
        mu = s if mu is None else mu_momentum * mu + (1 - mu_momentum) * s
        betas.append(1.0 - min(1.0, mu / (gamma * j * max(j - i, 1))))
    return np.asarray(betas, np.float64)


# ---------------------------------------------------------------------------
# Programs
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _programs(loss: Callable, lr: float):
    """The reference's jitted programs over a family's ``loss``.
    ``upload`` is the eq. (3) blend followed by local SGD from the
    blended model over a fixed number of (batch, valid) steps, a step
    marked invalid leaving the model as it is; with c0 = 1, c1 = 0 it is
    plain local training from the first argument.  ``fleet`` is that
    local training of every client from one model, vmapped over the
    clients, as a fleet-wide round is written.  ``accumulate`` adds one
    client's row, picked from the fleet's stacked rows, times its
    weight.  Every local round runs with the cell's padded step count,
    since each compile of an SGD step at "highest" precision takes
    minutes on a TPU host."""
    import jax
    import jax.numpy as jnp

    def train(w, batches, valid, data):
        def body(w, xs):
            batch, v = xs
            d = jax.grad(loss)(w, batch, data)
            new = jax.tree.map(lambda a, b: a - lr * b, w, d)
            return jax.tree.map(lambda a, b: jnp.where(v, a, b), new, w), None

        return jax.lax.scan(body, w, (batches, valid))[0]

    def upload(g, row, c0, c1, batches, valid, data):
        g2 = jax.tree.map(lambda a, b: c0 * a + c1 * b, g, row)
        return g2, train(g2, batches, valid, data)

    def accumulate(acc, rows, i, c):
        return jax.tree.map(lambda a, b: a + c * b[i], acc, rows)

    fleet = jax.vmap(train, in_axes=(None, 0, 0, None))
    return jax.jit(upload), jax.jit(fleet), jax.jit(accumulate)


@functools.lru_cache(maxsize=None)
def _blend_bf16():
    """eq. (3) with its operands, coefficients and sum in bfloat16, kept
    as float32: the ``blend_bf16`` fault."""
    import jax
    import jax.numpy as jnp

    def blend(g, row, c0, c1):
        bf = jnp.bfloat16
        return jax.tree.map(
            lambda a, b: (c0.astype(bf) * a.astype(bf)
                          + c1.astype(bf) * b.astype(bf)).astype(a.dtype),
            g, row)

    return jax.jit(blend)


# ---------------------------------------------------------------------------
# Runs
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RefRun:
    """What one reference run produced: final global params (a pytree of
    host arrays), and for the AFL runs the uploaders and betas in order
    and the global after each upload j in ``marks``."""
    params: Any
    cids: Optional[np.ndarray] = None
    betas: Optional[np.ndarray] = None
    marks: Dict[int, Any] = dataclasses.field(default_factory=dict)


class Reference:
    """The plain replay for one configuration (``config``, its whole
    file) of model family ``family`` (a ``families/<name>.py`` module),
    on the training data made from ``data_seed``."""

    def __init__(self, family, config: Dict[str, Any], data_seed: int):
        import jax
        import jax.numpy as jnp

        self.family, self.config = family, config
        self.task = config["task"]
        self.precision, self.fault = "highest", None
        data, self.keys = family.dataset(config, data_seed)
        self.data_seed = data_seed
        self.data = jax.tree.map(jnp.asarray, data)
        self._upload, self._fleet_round, self._acc = _programs(
            family.loss, float(self.task["lr"]))

    def _set(self, precision: str, fault: Optional[str]) -> None:
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.precision, self.fault = precision, fault

    # -- pieces ---------------------------------------------------------------
    def _batches(self, shards, cid, k, seed, steps):
        """The family's minibatches of one local round, padded with
        invalid steps to ``steps``, and the (steps,) valid mask."""
        import jax

        b = self.family.batches(self.config, shards[cid], cid, k, seed)
        if self.fault == "half_batch":
            b = jax.tree.map(lambda a: a[:, :max(1, a.shape[1] // 2)], b)
        n = jax.tree.leaves(b)[0].shape[0]
        b = jax.tree.map(lambda a: np.concatenate(
            [a, np.zeros((steps - n,) + a.shape[1:], a.dtype)]), b)
        return b, np.arange(steps) < n

    def _steps(self, ks) -> int:
        """The cell's padded local-round length: the next power of two
        of its longest round."""
        n = self.family.local_steps(self.config, max(ks))
        return 1 << (n - 1).bit_length()

    def _train(self, w, shards, cid, k, seed, steps):
        """Local SGD of client ``cid`` from ``w``."""
        one = np.float32(1.0)
        b, valid = self._batches(shards, cid, k, seed, steps)
        return self._upload(w, w, one, one - one, b, valid, self.data)[1]

    def _train_fleet(self, w, shards, ks, seed, steps):
        """Every client's local SGD from ``w``: the rows stacked on a
        leading client axis."""
        import jax

        got = [self._batches(shards, c, ks[c], seed, steps)
               for c in range(len(ks))]
        b = jax.tree.map(lambda *a: np.stack(a), *[one for one, _ in got])
        valid = np.stack([v for _, v in got])
        return self._fleet_round(w, b, valid, self.data)

    def _fleet(self, fleet: Dict[str, Any]):
        return fleet_steps(
            self.task["num_clients"], tau=fleet["tau"],
            hetero_a=fleet["hetero_a"], seed=fleet["seed"],
            adaptive=fleet["adaptive"], min_steps=fleet["min_steps"],
            max_steps=fleet["max_steps"],
            base_local_steps=fleet["base_local_steps"])

    @staticmethod
    def _host(tree):
        import jax

        return jax.tree.map(np.asarray, tree)

    # -- entries --------------------------------------------------------------
    def afl(self, *, seed: int, w0, shards, fleet: Dict[str, Any],
            iterations: int, tau_u: float, tau_d: float, gamma: float,
            mu_momentum: float, precision: str = "highest",
            fault: Optional[str] = None, marks=()) -> RefRun:
        """One CSMAAFL run: every client trains from w0 (retrain seed
        seed·100003), then each upload j blends the uploader's model in
        with beta_j and the uploader retrains from the new global (seed
        seed·100003 + j).  The global after each upload j in ``marks``
        is kept."""
        import jax

        self._set(precision, fault)
        taus, ks = self._fleet(fleet)
        events = schedule(taus, ks, tau_u=tau_u, tau_d=tau_d,
                          iterations=iterations)
        betas = csmaafl_betas(events, gamma=gamma, mu_momentum=mu_momentum)
        if self.fault == "alter_answer" and len(events) > 1:
            # the middle upload is credited to the next client over, and
            # blended in with a beta 0.01 off
            k = len(events) // 2
            c, j, i = events[k]
            events[k] = ((c + 1) % len(ks), j, i)
            betas = betas.copy()
            betas[k] = betas[k] - 0.01 if betas[k] > 0.5 else betas[k] + 0.01
        steps = self._steps(ks)
        one = np.float32(1.0)
        kept = {}
        with jax.default_matmul_precision(self.precision):
            g = w0
            fleet = self._train_fleet(w0, shards, ks, seed * 100003, steps)
            rows = [jax.tree.map(lambda v: v[c], fleet)
                    for c in range(len(ks))]
            del fleet
            for (c, j, _i), beta in zip(events, betas):
                if self.fault == "blend_coef":
                    beta = beta - 0.01 if beta > 0.5 else beta + 0.01
                c0 = np.float32(beta)
                c1 = one - c0
                b, valid = self._batches(shards, c, ks[c],
                                         seed * 100003 + j, steps)
                if self.fault == "blend_bf16":
                    # blended apart, then trained from: 1·g + 0·g is g
                    g = _blend_bf16()(g, rows[c], c0, c1)
                    c0, c1 = one, one - one
                    g, rows[c] = self._upload(g, g, c0, c1, b, valid,
                                              self.data)
                else:
                    g, rows[c] = self._upload(g, rows[c], c0, c1, b,
                                              valid, self.data)
                if j in marks:
                    kept[j] = self._host(g)
            params = self._host(g)
        return RefRun(params, np.asarray([e[0] for e in events], np.int32),
                      betas, kept)

    def retrain(self, *, w, seed: int, shards, fleet: Dict[str, Any],
                cid: int, j: int, precision: str = "highest",
                fault: Optional[str] = None):
        """Client ``cid``'s retrain after upload ``j`` of run ``seed``,
        from the global ``w``: K_cid local iterations, seed
        seed·100003 + j."""
        import jax

        self._set(precision, fault)
        _taus, ks = self._fleet(fleet)
        with jax.default_matmul_precision(self.precision):
            return self._host(self._train(w, shards, cid, ks[cid],
                                          seed * 100003 + j,
                                          self._steps(ks)))

    def fedavg(self, *, seed: int, w0, shards, fleet: Dict[str, Any],
               rounds: int, precision: str = "highest",
               fault: Optional[str] = None) -> RefRun:
        """Synchronous FedAvg: each round every client trains K_m local
        iterations from the global (seed seed·100003 + round), and the
        new global is sum_m alpha_m·w_m, alpha_m = |D_m| / sum |D|."""
        import jax

        self._set(precision, fault)
        _taus, ks = self._fleet(fleet)
        n = np.asarray([len(s) for s in shards], np.float64)
        alpha = (n / n.sum()).astype(np.float32)
        if self.fault == "alter_answer":
            # one client's upload is counted in place of its neighbour's
            alpha = alpha.copy()
            alpha[1] += alpha[0]
            alpha[0] = 0.0
        steps = self._steps(ks)
        with jax.default_matmul_precision(self.precision):
            g = w0
            for rnd in range(1, rounds + 1):
                rows = self._train_fleet(g, shards, ks, seed * 100003 + rnd,
                                         steps)
                acc = jax.tree.map(lambda a: 0.0 * a, g)
                for c in range(len(ks)):
                    acc = self._acc(acc, rows, c, alpha[c])
                g = acc
            params = self._host(g)
        return RefRun(params)

    def shards(self, kind: str, seed: int, **kw) -> List[np.ndarray]:
        return partition(kind, self.keys, self.task["num_clients"],
                         seed, **kw)

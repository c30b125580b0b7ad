"""Plain float32 reference of the benchmarked federated runs.

It imports nothing of the program under test (``repro``) and takes
nothing the program made.  From a configuration, a traffic mix and a
seed it rebuilds every input itself and replays the run the paper
describes, one step at a time:

* the procedural MNIST-like training set (the same generator, vectorized:
  one draw of all the pixel noise is the same stream as one draw per
  image);
* the IID and label-shard partitions, the heterogeneous fleet (compute
  time log-uniform in [tau, a*tau], adaptive K) and each client's
  seeded minibatch order;
* the CSMAAFL channel scheduler (one upload at a time, the older model
  first) and the eq. (11) coefficient with its staleness EMA run as the
  plain recurrence;
* the paper CNN (2 conv, 2 max-pool, 2 FC, log-softmax) and plain SGD;
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
tests: ``half_batch`` trains on the first half of each minibatch and
takes the mean over it; ``alter_answer`` hands one upload to another
client with a beta 0.01 off (FedAvg: one client's weight to another);
``blend_bf16`` computes every eq. (3) blend in bfloat16, and
``blend_coef`` every one with a coefficient 0.01 off.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Any, Dict, List, Optional

import numpy as np

FAULTS = (None, "half_batch", "alter_answer", "blend_bf16", "blend_coef")
IMAGE_SIZE = 28
NUM_CLASSES = 10
# how many images the generator renders at a time (bounds host memory)
_RENDER_CHUNK = 8192


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------
def _smooth(img: np.ndarray, iters: int) -> np.ndarray:
    for _ in range(iters):
        img = (img
               + np.roll(img, 1, 0) + np.roll(img, -1, 0)
               + np.roll(img, 1, 1) + np.roll(img, -1, 1)) / 5.0
    return img


def _class_template(cls: int, variant: str) -> np.ndarray:
    rng = np.random.default_rng(1000 + cls)
    img = rng.normal(0, 1, (IMAGE_SIZE, IMAGE_SIZE))
    img = _smooth(img, 3 if variant == "digits" else 6)
    q = np.quantile(img, 0.72)
    img = np.where(img > q, 1.0, 0.0)
    img = _smooth(img, 1)
    return img.astype(np.float32)


def _render(templates, labels, rng, noise) -> np.ndarray:
    n = len(labels)
    shifts = rng.integers(-3, 4, size=(n, 2))
    scales = rng.uniform(0.7, 1.3, size=n)
    out = np.empty((n, IMAGE_SIZE, IMAGE_SIZE, 1), np.float32)
    ar = np.arange(IMAGE_SIZE)
    for a in range(0, n, _RENDER_CHUNK):
        b = min(n, a + _RENDER_CHUNK)
        # np.roll by s: out[i] = in[(i - s) mod 28], per image and axis
        r = (ar[None, :] - shifts[a:b, 0:1]) % IMAGE_SIZE
        c = (ar[None, :] - shifts[a:b, 1:2]) % IMAGE_SIZE
        t = templates[labels[a:b]]
        img = t[np.arange(b - a)[:, None, None], r[:, :, None],
                c[:, None, :]]
        img = (img * scales[a:b, None, None]
               + rng.normal(0, noise, (b - a, IMAGE_SIZE, IMAGE_SIZE)))
        out[a:b, :, :, 0] = img
    return np.clip(out, 0.0, 1.5)


def training_set(variant: str, train_n: int, test_n: int, seed: int):
    """(images (N, 28, 28, 1) f32, labels (N,) int32) of the training
    split; the test labels are drawn (and dropped) to keep the stream."""
    if variant not in ("digits", "fashion"):
        raise ValueError(f"unknown data variant {variant!r}")
    templates = np.stack([_class_template(c, variant)
                          for c in range(NUM_CLASSES)])
    noise = 0.20 if variant == "digits" else 0.30
    rng = np.random.default_rng(seed + (0 if variant == "digits" else 77))
    train_y = rng.integers(0, NUM_CLASSES, train_n).astype(np.int32)
    rng.integers(0, NUM_CLASSES, test_n)
    return _render(templates, train_y, rng, noise), train_y


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


def batch_indices(indices: np.ndarray, cid: int, batch_size: int,
                  num_batches: int, seed: int) -> np.ndarray:
    """(num_batches, batch_size) indices into the training set: the
    client's shard visited in a seeded random order, reshuffled when an
    epoch runs out."""
    rng = np.random.default_rng((seed * 9176 + cid) % (2 ** 63))
    n = len(indices)
    order = rng.permutation(n)
    ptr = 0
    rows = []
    for _ in range(num_batches):
        if ptr + batch_size > n:
            order = rng.permutation(n)
            ptr = 0
        rows.append(order[ptr:ptr + batch_size])
        ptr += batch_size
    return indices[np.stack(rows)].astype(np.int32)


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
# Model
# ---------------------------------------------------------------------------
def init_params(model: Dict[str, int], seed: int):
    """He-normal paper CNN from ``jax.random.PRNGKey(seed)``; biases 0."""
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    ksz, c1, c2 = model["kernel"], model["conv1"], model["conv2"]
    flat = (model["image_size"] // 4) ** 2 * c2

    def he(kk, shape, fan_in):
        return (jax.random.normal(kk, shape)
                * jnp.sqrt(2.0 / fan_in)).astype(jnp.float32)

    return {
        "conv1_w": he(k[0], (ksz, ksz, model["channels"], c1),
                      ksz * ksz * model["channels"]),
        "conv1_b": jnp.zeros((c1,), jnp.float32),
        "conv2_w": he(k[1], (ksz, ksz, c1, c2), ksz * ksz * c1),
        "conv2_b": jnp.zeros((c2,), jnp.float32),
        "fc1_w": he(k[2], (flat, model["fc"]), flat),
        "fc1_b": jnp.zeros((model["fc"],), jnp.float32),
        "fc2_w": he(k[3], (model["fc"], model["num_classes"]), model["fc"]),
        "fc2_b": jnp.zeros((model["num_classes"],), jnp.float32),
    }


def _forward(p, x):
    import jax

    def conv(x, w, b):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b

    def pool(x):
        return jax.lax.reduce_window(x, -np.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = pool(jax.nn.relu(conv(x, p["conv1_w"], p["conv1_b"])))
    x = pool(jax.nn.relu(conv(x, p["conv2_w"], p["conv2_b"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1_w"] + p["fc1_b"])
    return jax.nn.log_softmax(x @ p["fc2_w"] + p["fc2_b"], axis=-1)


def _loss(p, x, y):
    import jax.numpy as jnp

    logp = _forward(p, x)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@functools.lru_cache(maxsize=None)
def _programs(lr: float):
    """The reference's jitted programs.  ``upload`` is the eq. (3)
    blend followed by local SGD from the blended model over a fixed
    number of (index row, valid) steps, a step marked invalid leaving the
    model as it is; with c0 = 1, c1 = 0 it is plain local training from
    the first argument.  ``fleet`` is that local training of every
    client from one model, vmapped over the clients, as a fleet-wide
    round is written.  ``accumulate`` adds one client's row, picked from
    the fleet's stacked rows, times its weight.  Every local round runs
    with the cell's padded step count, since each compile of an SGD step
    at "highest" precision takes minutes on a TPU host."""
    import jax
    import jax.numpy as jnp

    def train(w, idx, valid, x, y):
        def body(w, xs):
            ib, v = xs
            d = jax.grad(_loss)(w, x[ib], y[ib])
            new = jax.tree.map(lambda a, b: a - lr * b, w, d)
            return jax.tree.map(lambda a, b: jnp.where(v, a, b), new, w), None

        return jax.lax.scan(body, w, (idx, valid))[0]

    def upload(g, row, c0, c1, idx, valid, x, y):
        g2 = jax.tree.map(lambda a, b: c0 * a + c1 * b, g, row)
        return g2, train(g2, idx, valid, x, y)

    def accumulate(acc, rows, i, c):
        return jax.tree.map(lambda a, b: a + c * b[i], acc, rows)

    fleet = jax.vmap(train, in_axes=(None, 0, 0, None, None))
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
    """What one reference run produced: final global params (host
    numpy), and for the AFL runs the uploaders and betas in order and
    the global after each upload j in ``marks``."""
    params: Dict[str, np.ndarray]
    cids: Optional[np.ndarray] = None
    betas: Optional[np.ndarray] = None
    marks: Dict[int, Dict[str, np.ndarray]] = dataclasses.field(
        default_factory=dict)


class Reference:
    """The plain replay for one configuration.  ``task`` is the
    configuration's ``task`` block, ``model`` its ``model`` block."""

    def __init__(self, model: Dict[str, int], task: Dict[str, Any],
                 data_seed: int):
        import jax.numpy as jnp

        self.model, self.task = model, task
        self.precision, self.fault = "highest", None
        x, y = training_set(task["variant"], task["train_n"],
                            task["test_n"], data_seed)
        self.labels = y
        self.data_seed = data_seed
        self.x, self.y = jnp.asarray(x), jnp.asarray(y)
        self._upload, self._fleet_round, self._acc = _programs(
            float(task["lr"]))

    def _set(self, precision: str, fault: Optional[str]) -> None:
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.precision, self.fault = precision, fault

    # -- pieces ---------------------------------------------------------------
    def _idx(self, shards, cid, k, seed, steps):
        """(steps, B) minibatch indices of one local round, padded with
        invalid steps, and the (steps,) valid mask."""
        idx = batch_indices(shards[cid], cid, self.task["batch_size"],
                            k * self.task["local_batches_per_step"], seed)
        if self.fault == "half_batch":
            idx = idx[:, :max(1, idx.shape[1] // 2)]
        n = idx.shape[0]
        pad = np.zeros((steps - n, idx.shape[1]), idx.dtype)
        return np.concatenate([idx, pad]), np.arange(steps) < n

    def _steps(self, ks) -> int:
        """The cell's padded local-round length: the next power of two
        of its longest round."""
        n = max(ks) * self.task["local_batches_per_step"]
        return 1 << (n - 1).bit_length()

    def _train(self, w, shards, cid, k, seed, steps):
        """Local SGD of client ``cid`` from ``w``."""
        one = np.float32(1.0)
        idx, valid = self._idx(shards, cid, k, seed, steps)
        return self._upload(w, w, one, one - one, idx, valid, self.x,
                            self.y)[1]

    def _train_fleet(self, w, shards, ks, seed, steps):
        """Every client's local SGD from ``w``: the rows stacked on a
        leading client axis."""
        got = [self._idx(shards, c, ks[c], seed, steps)
               for c in range(len(ks))]
        idx = np.stack([i for i, _ in got])
        valid = np.stack([v for _, v in got])
        return self._fleet_round(w, idx, valid, self.x, self.y)

    def _fleet(self, fleet: Dict[str, Any]):
        return fleet_steps(
            self.task["num_clients"], tau=fleet["tau"],
            hetero_a=fleet["hetero_a"], seed=fleet["seed"],
            adaptive=fleet["adaptive"], min_steps=fleet["min_steps"],
            max_steps=fleet["max_steps"],
            base_local_steps=fleet["base_local_steps"])

    @staticmethod
    def _host(tree) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in tree.items()}

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
            rows = [{k: v[c] for k, v in fleet.items()}
                    for c in range(len(ks))]
            del fleet
            for (c, j, _i), beta in zip(events, betas):
                if self.fault == "blend_coef":
                    beta = beta - 0.01 if beta > 0.5 else beta + 0.01
                c0 = np.float32(beta)
                c1 = one - c0
                idx, valid = self._idx(shards, c, ks[c], seed * 100003 + j,
                                       steps)
                if self.fault == "blend_bf16":
                    # blended apart, then trained from: 1·g + 0·g is g
                    g = _blend_bf16()(g, rows[c], c0, c1)
                    c0, c1 = one, one - one
                    g, rows[c] = self._upload(g, g, c0, c1, idx, valid,
                                              self.x, self.y)
                else:
                    g, rows[c] = self._upload(g, rows[c], c0, c1, idx,
                                              valid, self.x, self.y)
                if j in marks:
                    kept[j] = self._host(g)
            params = self._host(g)
        return RefRun(params, np.asarray([e[0] for e in events], np.int32),
                      betas, kept)

    def retrain(self, *, w, seed: int, shards, fleet: Dict[str, Any],
                cid: int, j: int, precision: str = "highest",
                fault: Optional[str] = None) -> Dict[str, np.ndarray]:
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
        return partition(kind, self.labels, self.task["num_clients"],
                         seed, **kw)

"""granite-4.0-h-micro as a federated LoRA client over a frozen base (the
contract is in ``families/__init__.py``).

A client's row is its LoRA adapters (Hu et al., arXiv 2106.09685) on
every linear layer of every block (QLoRA's targets, arXiv 2305.14314),
exchanged over a frozen base as federated LLM fine-tuning does (FedIT,
arXiv 2305.05644; OpenFedLLM, arXiv 2402.06954).  Written from the
published description (the model's ``config.json`` and the
GraniteMoeHybrid layer equations), in plain float32 ``jax.numpy``:

* h = E[tokens] * embedding_multiplier;
* each layer: h += r * mixer(RMSNorm(h)); h += r * MLP(RMSNorm(h)), r the
  residual multiplier;
* the mixer of a ``mamba`` layer is Mamba-2: ``in_proj`` to z, xBC and
  dt; a causal depthwise conv with bias and silu over xBC, split into x,
  B and C; dt = softplus(dt + dt_bias), A = -exp(A_log); the SSD scan
  computed by chunks as the Mamba-2 paper's listing does (arXiv
  2405.21060, ``ssd_minimal_discrete``); y + D * x; RMSNorm(y * silu(z));
  ``out_proj``;
* the mixer of an ``attention`` layer is grouped-query attention with
  no position embedding, scores times the attention multiplier, causal;
* the MLP: ``input_linear`` to [gate, up], silu(gate) * up,
  ``output_linear``;
* logits = RMSNorm(h) E^T / logits_scaling; the loss is the mean
  next-token cross entropy, the logits formed a chunk of the sequence at
  a time so that they fit.

Every adapted linear is x W + (alpha / r) (x A) B, A (fan_in, r) and B
(r, fan_out); only the adapters are differentiated.  Each layer is
recomputed in the backward pass (remat), so a fleet-wide round of 8
clients fits one chip beside the 3.8 GB base.

The data are the token corpus and the frozen base, both drawn from the
data seed on the device (the base is 3.8 GB: the replay takes them as
they are).  The base's draw (``assumed.base_init`` in the
configuration) names each weight by its layer and name, so the program
can draw the same base without sharing this code.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.families.paper_cnn import batch_indices

# the adapted weights of each layer kind, in their draw order
TARGETS = {"mamba": ("in_proj", "out_proj", "w_in", "w_out"),
           "attention": ("wq", "wk", "wv", "wo", "w_in", "w_out")}
# the base's draw order (``assumed.base_init``)
BASE_NAMES = ("embed", "in_proj", "conv_w", "conv_b", "A_log", "dt_bias",
              "D", "out_proj", "wq", "wk", "wv", "wo", "w_in", "w_out")
# tokens of the vocabulary projection formed at a time in the loss
_CE_CHUNK = 128


@dataclasses.dataclass(frozen=True)
class ModelBlock:
    """The configuration's ``model`` block riding in the data as pytree
    metadata (no leaves): the programs that take the data are traced,
    and cached, per block, and ``loss`` reads its shapes from it."""
    text: str

    @property
    def block(self) -> Dict[str, Any]:
        return json.loads(self.text)


jax.tree_util.register_static(ModelBlock)


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------
def _kinds(model) -> List[str]:
    return list(model["layer_types"][:model["num_hidden_layers"]])


def _dims(model) -> Dict[str, int]:
    d = model["hidden_size"]
    hd = d // model["num_attention_heads"]
    di = model["mamba_expand"] * d
    gn = model["mamba_n_groups"] * model["mamba_d_state"]
    return {"d": d, "hd": hd, "di": di, "gn": gn, "conv": di + 2 * gn,
            "ff": model["shared_intermediate_size"],
            "h": model["num_attention_heads"],
            "kv": model["num_key_value_heads"],
            "mh": model["mamba_n_heads"], "V": model["vocab_size"]}


def _matrices(model, kind: str) -> Dict[str, tuple]:
    """(fan_in, fan_out) of each linear weight of a layer of ``kind``."""
    x = _dims(model)
    d = x["d"]
    mlp = {"w_in": (d, 2 * x["ff"]), "w_out": (x["ff"], d)}
    if kind == "mamba":
        return dict(in_proj=(d, 2 * x["di"] + 2 * x["gn"] + x["mh"]),
                    out_proj=(x["di"], d), **mlp)
    return dict(wq=(d, x["h"] * x["hd"]), wk=(d, x["kv"] * x["hd"]),
                wv=(d, x["kv"] * x["hd"]), wo=(x["h"] * x["hd"], d), **mlp)


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------
def corpus(task: Dict[str, Any], vocab: int, seq_len: int, seed: int):
    """(tokens (train + eval, seq_len + 1), topics): sequence i keeps to
    its topic's block of the vocabulary, each token in it with
    probability 3/4 (uniform inside), otherwise uniform over the
    vocabulary; one numpy stream: topics, in-block draws, block offsets,
    free draws."""
    rng = np.random.default_rng(seed)
    n = task["train_seqs"] + task["eval_seqs"]
    block = vocab // task["num_topics"]
    shape = (n, seq_len + 1)
    topics = rng.integers(0, task["num_topics"], n).astype(np.int32)
    inside = rng.random(shape) < 0.75
    local = topics[:, None] * block + rng.integers(0, block, shape)
    free = rng.integers(0, vocab, shape)
    return np.where(inside, local, free).astype(np.int32), topics


def frozen_base(model, seed: int):
    """The base, weight by weight: the key of weight ``name`` of layer l
    is fold_in(fold_in(PRNGKey(seed), l), BASE_NAMES.index(name)), the
    embedding's l the number of layers.  Matrices N(0, 1) / sqrt(fan_in)
    as (fan_in, fan_out); the embedding N(0, 1) * 0.02; the conv N(0, 1)
    / sqrt(d_conv), its bias 0; A_log = log U(1, 16); dt_bias the inverse
    softplus of exp U(log 1e-3, log 1e-1); D = 1; norm weights 1."""
    x = _dims(model)
    root = jax.random.PRNGKey(seed)

    def key(layer, name):
        return jax.random.fold_in(jax.random.fold_in(root, layer),
                                  BASE_NAMES.index(name))

    def matrix(layer, name, shape):
        return jax.random.normal(key(layer, name), shape) / math.sqrt(
            shape[0])

    ones = jnp.ones
    layers = []
    for i, kind in enumerate(_kinds(model)):
        p = {name: matrix(i, name, shape)
             for name, shape in _matrices(model, kind).items()}
        p["norm"] = ones((x["d"],))
        p["mlp_norm"] = ones((x["d"],))
        if kind == "mamba":
            k = model["mamba_d_conv"]
            p["conv_w"] = jax.random.normal(key(i, "conv_w"),
                                            (k, x["conv"])) / math.sqrt(k)
            p["conv_b"] = jnp.zeros((x["conv"],))
            p["A_log"] = jnp.log(jax.random.uniform(
                key(i, "A_log"), (x["mh"],), minval=1.0, maxval=16.0))
            dt = jnp.exp(jax.random.uniform(
                key(i, "dt_bias"), (x["mh"],), minval=math.log(1e-3),
                maxval=math.log(1e-1)))
            p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            p["D"] = ones((x["mh"],))
            p["gate_norm"] = ones((x["di"],))
        layers.append(p)
    embed = 0.02 * jax.random.normal(key(len(layers), "embed"),
                                     (x["V"], x["d"]))
    return {"embed": embed, "layers": layers, "final_norm": ones((x["d"],))}


def dataset(config: Dict[str, Any], seed: int):
    """({"tokens": corpus, "base": frozen base} on the device, with the
    ``model`` block as metadata; the training sequences' topics as the
    partitions' keys)."""
    m, t = config["model"], config["task"]
    tokens, topics = corpus(t, m["vocab_size"], m["seq_len"], seed)
    data = {"tokens": jnp.asarray(tokens), "base": frozen_base(m, seed),
            "model": ModelBlock(json.dumps(m, sort_keys=True))}
    return data, topics[:t["train_seqs"]]


def local_steps(config: Dict[str, Any], k: int) -> int:
    return k * config["task"]["local_batches_per_step"]


def batches(config: Dict[str, Any], shard: np.ndarray, cid: int, k: int,
            seed: int) -> np.ndarray:
    """Index rows into the corpus, (steps, batch_size) sequences."""
    return batch_indices(shard, cid, config["task"]["batch_size"],
                         local_steps(config, k), seed)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------
def init_params(model: Dict[str, Any], seed: int):
    """The adapters: one dict per layer, target -> {"a", "b"}; A of
    target j of layer l from fold_in(fold_in(PRNGKey(seed), l), j),
    N(0, 1) / sqrt(fan_in); B zero."""
    r = model["lora"]["rank"]
    root = jax.random.PRNGKey(seed)
    out = []
    for i, kind in enumerate(_kinds(model)):
        shapes = _matrices(model, kind)
        layer = {}
        for j, name in enumerate(TARGETS[kind]):
            fan_in, fan_out = shapes[name]
            k = jax.random.fold_in(jax.random.fold_in(root, i), j)
            layer[name] = {"a": jax.random.normal(k, (fan_in, r))
                           / math.sqrt(fan_in),
                           "b": jnp.zeros((r, fan_out), jnp.float32)}
        out.append(layer)
    return out


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _segsum(x):
    """Segment sums: out[..., i, j] = x[..., j+1] + ... + x[..., i]
    for j <= i, -inf above the diagonal."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def _ssd(xdt, a_dt, b, c, chunk: int):
    """The SSD scan by chunks (arXiv 2405.21060, Listing 1), from a zero
    state.  xdt (n, L, H, P) = dt * x; a_dt (n, L, H) = dt * A; b, c
    (n, L, G, N), each group shared by H / G heads.  Returns y (n, L, H,
    P)."""
    n, L, H, P = xdt.shape
    G, N = b.shape[-2:]
    nc = L // chunk
    X = xdt.reshape(n, nc, chunk, G, H // G, P)
    A = a_dt.reshape(n, nc, chunk, G, H // G).transpose(0, 3, 4, 1, 2)
    B = b.reshape(n, nc, chunk, G, N)
    C = c.reshape(n, nc, chunk, G, N)
    a_cum = jnp.cumsum(A, -1)                          # (n, G, R, nc, Q)
    # within each chunk
    decay = jnp.exp(_segsum(A))                        # (n, G, R, nc, Q, Q)
    cb = jnp.einsum("bclgn,bcsgn->bgcls", C, B)        # (n, G, nc, Q, Q)
    y_diag = jnp.einsum("bgcls,bgrcls,bcsgrp->bclgrp", cb, decay, X)
    # each chunk's state, then the states passed from chunk to chunk
    to_end = jnp.exp(a_cum[..., -1:] - a_cum)
    states = jnp.einsum("bclgn,bgrcl,bclgrp->bcgrpn", B, to_end, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], [(0, 0)] * 3
                                          + [(1, 0)])))
    states = jnp.einsum("bgrzc,bcgrpn->bzgrpn", chunk_decay, states)[:, :-1]
    # each chunk's output from the state it starts with
    y_off = jnp.einsum("bclgn,bcgrpn,bgrcl->bclgrp", C, states,
                       jnp.exp(a_cum))
    return (y_diag + y_off).reshape(n, L, H, P)


def _layer(model, kind: str, p, ad, h):
    """One layer: the mixer and the MLP sublayers, residuals scaled."""
    x = _dims(model)
    eps = model["rms_norm_eps"]
    r = model["residual_multiplier"]
    s = model["lora"]["alpha"] / model["lora"]["rank"]

    def lin(v, name):
        return v @ p[name] + s * ((v @ ad[name]["a"]) @ ad[name]["b"])

    n, S, _ = h.shape
    u = _rmsnorm(h, p["norm"], eps)
    if kind == "mamba":
        di, gn, mh = x["di"], x["gn"], x["mh"]
        zxbcdt = lin(u, "in_proj")
        z = zxbcdt[..., :di]
        xbc = zxbcdt[..., di:2 * di + 2 * gn]
        dt = zxbcdt[..., 2 * di + 2 * gn:]
        k = model["mamba_d_conv"]
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        xbc = sum(padded[:, i:i + S] * p["conv_w"][i] for i in range(k))
        xbc = jax.nn.silu(xbc + p["conv_b"])
        xs = xbc[..., :di].reshape(n, S, mh, model["mamba_d_head"])
        g = model["mamba_n_groups"]
        b = xbc[..., di:di + gn].reshape(n, S, g, model["mamba_d_state"])
        c = xbc[..., di + gn:].reshape(n, S, g, model["mamba_d_state"])
        dt = jax.nn.softplus(dt + p["dt_bias"])
        a = -jnp.exp(p["A_log"])
        y = _ssd(xs * dt[..., None], dt * a, b, c,
                 model["mamba_chunk_size"])
        y = (y + xs * p["D"][:, None]).reshape(n, S, di)
        y = _rmsnorm(y * jax.nn.silu(z), p["gate_norm"], eps)
        mixed = lin(y, "out_proj")
    else:
        hd, kv, rep = x["hd"], x["kv"], x["h"] // x["kv"]
        q = lin(u, "wq").reshape(n, S, kv, rep, hd)
        kk = lin(u, "wk").reshape(n, S, kv, hd)
        v = lin(u, "wv").reshape(n, S, kv, hd)
        scores = jnp.einsum("bsgrd,btgd->bgrst", q, kk) \
            * model["attention_multiplier"]
        causal = jnp.tril(jnp.ones((S, S), bool))
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrst,btgd->bsgrd", w, v).reshape(n, S, -1)
        mixed = lin(o, "wo")
    h = h + r * mixed
    u = _rmsnorm(h, p["mlp_norm"], eps)
    gate, up = jnp.split(lin(u, "w_in"), 2, axis=-1)
    return h + r * lin(jax.nn.silu(gate) * up, "w_out")


def _hidden(model, base, adapters, tokens):
    h = base["embed"][tokens] * model["embedding_multiplier"]
    for kind, p, ad in zip(_kinds(model), base["layers"], adapters):
        h = jax.checkpoint(functools.partial(_layer, model, kind))(p, ad, h)
    return _rmsnorm(h, base["final_norm"], model["rms_norm_eps"])


def _mean_nll(model, embed, h, labels):
    """Mean next-token NLL, the logits formed at most ``_CE_CHUNK``
    positions at a time (each chunk recomputed in the backward pass)."""
    n, S, d = h.shape
    c = math.gcd(S, _CE_CHUNK)
    hs = h.reshape(n, S // c, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(n, S // c, c).transpose(1, 0, 2)

    def chunk(total, xs):
        hc, lc = xs
        logits = (hc @ embed.T) / model["logits_scaling"]
        picked = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return total + jnp.sum(jax.nn.logsumexp(logits, -1) - picked), None

    total, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.float32(0.0),
                            (hs, ls))
    return total / (n * S)


def loss(adapters, batch, data):
    """Mean next-token cross entropy of the sequences at the index row
    ``batch``."""
    model = data["model"].block
    seqs = data["tokens"][batch]
    h = _hidden(model, data["base"], adapters, seqs[:, :-1])
    return _mean_nll(model, data["base"]["embed"], h, seqs[:, 1:])


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------
def param_count(model: Dict[str, Any]) -> int:
    """The row: r x (fan_in + fan_out) for every adapted weight."""
    r = model["lora"]["rank"]
    return sum(r * (fi + fo) for kind in _kinds(model)
               for fi, fo in (_matrices(model, kind)[t]
                              for t in TARGETS[kind]))


def base_param_count(model: Dict[str, Any]) -> int:
    """The frozen base: the layers, the tied embedding and the final
    norm."""
    x = _dims(model)
    total = x["V"] * x["d"] + x["d"]
    for kind in _kinds(model):
        total += sum(fi * fo for fi, fo in _matrices(model, kind).values())
        total += 2 * x["d"]
        if kind == "mamba":
            total += (model["mamba_d_conv"] + 1) * x["conv"] \
                + 3 * x["mh"] + x["di"]
    return total


def _macs_per_token(model: Dict[str, Any]) -> Dict[str, int]:
    """Multiply-adds per token of the forward pass, by what the backward
    pass does with them: ``weight`` products of an activation with a
    frozen weight (the linear layers, the tied head, the depthwise conv);
    ``activation`` products of two activations (attention's scores and
    values over the whole causal square as it is computed; the chunked
    SSD's within-chunk C B^T and its product with x, the chunk states and
    the states' outputs); ``adapter`` the LoRA terms."""
    x = _dims(model)
    q, s = model["mamba_chunk_size"], model["seq_len"]
    weight = x["V"] * x["d"]
    activation = 0
    for kind in _kinds(model):
        weight += sum(fi * fo for fi, fo in _matrices(model, kind).values())
        if kind == "mamba":
            weight += model["mamba_d_conv"] * x["conv"]
            p = model["mamba_d_head"]
            activation += (x["gn"] * q + x["mh"] * q * p
                           + 2 * x["mh"] * p * model["mamba_d_state"])
        else:
            activation += 2 * s * x["h"] * x["hd"]
    return {"weight": weight, "activation": activation,
            "adapter": param_count(model)}


def forward_flops_per_sample(model: Dict[str, Any]) -> int:
    """2 x the multiply-adds of one sequence's forward pass."""
    return 2 * model["seq_len"] * sum(_macs_per_token(model).values())


def train_flops_per_sample(model: Dict[str, Any]) -> int:
    """One sequence's SGD step on the adapters: the forward pass, and a
    backward pass that forms the input gradients of every product and the
    gradients of the adapters alone.  A product with a frozen weight
    counts twice, every other three times; the forward that remat runs
    again is not counted."""
    m = _macs_per_token(model)
    return 2 * model["seq_len"] * (2 * m["weight"] + 3 * m["activation"]
                                   + 3 * m["adapter"])


# ---------------------------------------------------------------------------
# Program side
# ---------------------------------------------------------------------------
def model_config(model: Dict[str, Any]):
    """``repro``'s granite-4.0-h-micro at the ``model`` block's widths
    and depth."""
    from repro.configs import get_config
    from repro.configs.base import ATTN_GLOBAL, MAMBA

    cfg = get_config("granite-4.0-h-micro")
    kinds = {"mamba": MAMBA, "attention": ATTN_GLOBAL}
    return dataclasses.replace(
        cfg, num_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // model["num_attention_heads"],
        d_ff=model["shared_intermediate_size"],
        vocab_size=model["vocab_size"], norm_eps=model["rms_norm_eps"],
        embedding_multiplier=model["embedding_multiplier"],
        residual_multiplier=model["residual_multiplier"],
        logits_scaling=model["logits_scaling"],
        attention=dataclasses.replace(
            cfg.attention,
            pattern=tuple(kinds[k] for k in _kinds(model)),
            query_pre_attn_scalar=model["attention_multiplier"] ** -2),
        ssm=dataclasses.replace(
            cfg.ssm, d_state=model["mamba_d_state"],
            d_conv=model["mamba_d_conv"], expand=model["mamba_expand"],
            head_dim=model["mamba_d_head"],
            n_groups=model["mamba_n_groups"],
            chunk_size=model["mamba_chunk_size"]))


def task(config: Dict[str, Any], iid: bool, seed: int):
    """``repro.core.tasks.LoRALMTask`` at the configuration's widths."""
    from repro.core.tasks import LoRALMTask

    m, t = config["model"], config["task"]
    return LoRALMTask(
        model_config(m), rank=m["lora"]["rank"], alpha=m["lora"]["alpha"],
        iid=iid, num_clients=t["num_clients"], batch_size=t["batch_size"],
        seq_len=m["seq_len"],
        local_batches_per_step=t["local_batches_per_step"],
        train_seqs=t["train_seqs"], eval_seqs=t["eval_seqs"],
        num_topics=t["num_topics"], lr=t["lr"], seed=seed)


def samples_per_iteration(config: Dict[str, Any]) -> int:
    """Sequences one local iteration trains."""
    t = config["task"]
    return t["local_batches_per_step"] * t["batch_size"]

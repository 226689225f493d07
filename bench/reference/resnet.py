"""Plain reference for the CIFAR ResNets of FedPart (Wang et al., NeurIPS 2024,
Appendix A), in straightforward jax.numpy.  It imports nothing of the program
under test and takes nothing the program made.

It holds what the benchmark needs to know of the architecture:

- the weights, made on the device from the seed in one jitted call, as the
  nested dict the program's ResNet adapter reads (``stem``, ``blocks/NN``,
  ``head``; batch norms carry ``mean_ema``/``var_ema`` running moments, which
  train-mode batch norm never reads);
- the FedPart layer groups: the stem, then each block's first conv (with the
  block's shortcut conv) and second conv, then the classifier head;
- the forward FLOPs of each group per image, and its trained parameters;
- a federated round as the paper describes it: a cohort drawn without
  replacement, local Adam on the round's group for the client's shuffled
  batches, then the plain average of the trained leaves (equal client sizes).

Batch norm runs on batch statistics in training and in the eval, as the
program's federated loop does (running moments are client-local and never
averaged, paper Section 4).  The float32 reference computes its convolutions
and matmuls at "highest" precision.  The same code in bfloat16 (parameters,
activations and optimizer state) is the control that the comparison has to
fail.

The round reproduces the program's documented conventions for who trains on
what, so that both see the same rows in the same order: the cohort of each
round comes from one ``numpy.random.default_rng(seed)`` stream through Floyd's
algorithm, and client ``c``'s batches in round ``r`` are slices of one
permutation per epoch drawn from ``default_rng`` seeded with the 32-bit word
that ``numpy.random.SeedSequence((seed, r, c))`` generates.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5
ADAM_B1, ADAM_B2 = 0.9, 0.999
FULL = -1          # the group id of a full-network (FNU) round
STAT_KEYS = ("mean_ema", "var_ema")


# ---------------------------------------------------------------------------
# Architecture
# ---------------------------------------------------------------------------

def block_specs(cfg: dict) -> list[tuple[str, int, int, int]]:
    """(name, cin, cout, stride) of every residual block, in order.  The
    first block of every stage after the first halves the resolution and
    has a 1x1 shortcut conv."""
    out, cin, idx = [], cfg["channels"][0], 0
    for stage, (n, cout) in enumerate(zip(cfg["stages"], cfg["channels"])):
        for b in range(n):
            stride = 2 if (stage > 0 and b == 0) else 1
            out.append((f"{idx:02d}", cin, cout, stride))
            cin, idx = cout, idx + 1
    return out


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every trainable leaf's path and shape (running moments left out)."""
    c0, ncls = cfg["channels"][0], cfg["num_classes"]
    shapes = {"stem/conv/w": (3, 3, cfg["in_channels"], c0),
              "stem/bn/scale": (c0,), "stem/bn/bias": (c0,)}
    for name, cin, cout, stride in block_specs(cfg):
        p = f"blocks/{name}"
        shapes[f"{p}/conv1/w"] = (3, 3, cin, cout)
        shapes[f"{p}/bn1/scale"] = shapes[f"{p}/bn1/bias"] = (cout,)
        shapes[f"{p}/conv2/w"] = (3, 3, cout, cout)
        shapes[f"{p}/bn2/scale"] = shapes[f"{p}/bn2/bias"] = (cout,)
        if stride != 1:
            shapes[f"{p}/sc_conv/w"] = (1, 1, cin, cout)
            shapes[f"{p}/sc_bn/scale"] = shapes[f"{p}/sc_bn/bias"] = (cout,)
    shapes["head/w"] = (cfg["channels"][-1], ncls)
    shapes["head/b"] = (ncls,)
    return shapes


def num_groups(cfg: dict) -> int:
    return 2 + 2 * len(block_specs(cfg))


def group_of(path: str, cfg: dict) -> int:
    """FedPart group of a leaf (paper Appendix A)."""
    parts = path.split("/")
    if parts[0] == "stem":
        return 0
    if parts[0] == "head":
        return num_groups(cfg) - 1
    first = parts[2] in ("conv1", "bn1", "sc_conv", "sc_bn")
    return 1 + 2 * int(parts[1]) + (0 if first else 1)


def group_forward_flops(cfg: dict) -> list[float]:
    """Forward FLOPs per image of each group: 2 x MACs of its convs (and of
    the head's matmul).  Batch norm, ReLU and pooling are not counted."""
    hw = cfg["image_size"] ** 2
    flops = [0.0] * num_groups(cfg)
    flops[0] = 2.0 * 9 * cfg["in_channels"] * cfg["channels"][0] * hw
    for name, cin, cout, stride in block_specs(cfg):
        hw //= stride * stride
        g = 1 + 2 * int(name)
        flops[g] = 2.0 * 9 * cin * cout * hw
        if stride != 1:
            flops[g] += 2.0 * cin * cout * hw
        flops[g + 1] = 2.0 * 9 * cout * cout * hw
    flops[-1] = 2.0 * cfg["channels"][-1] * cfg["num_classes"]
    return flops


def group_trained_params(cfg: dict) -> list[int]:
    """Parameters each group trains (running moments are not trained)."""
    out = [0] * num_groups(cfg)
    for path, shape in param_shapes(cfg).items():
        out[group_of(path, cfg)] += int(np.prod(shape))
    return out


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *heads, last = path.split("/")
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = leaf
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    """Nested dict -> {path: leaf}, running moments left out."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten(v, path))
        elif k not in STAT_KEYS:
            out[path] = v
    return out


def make_params(key: jax.Array, cfg: dict) -> dict:
    """The initial weights as the program's nested dict, float32, in one
    jitted call: He-normal convs, a 0.01-normal head, unit/zero batch norms
    with zero/unit running moments."""
    shapes = param_shapes(cfg)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (path, shape) in zip(keys, shapes.items()):
            if path.endswith("/w") and len(shape) == 4:
                fan_in = shape[0] * shape[1] * shape[2]
                flat[path] = jax.random.normal(k, shape) * np.sqrt(2.0 / fan_in)
            elif path == "head/w":
                flat[path] = jax.random.normal(k, shape) * 0.01
            elif path.endswith("/scale"):
                flat[path] = jnp.ones(shape, jnp.float32)
            else:
                flat[path] = jnp.zeros(shape, jnp.float32)
            if path.endswith("/scale"):
                stem = path[: -len("scale")]
                flat[stem + "mean_ema"] = jnp.zeros(shape, jnp.float32)
                flat[stem + "var_ema"] = jnp.ones(shape, jnp.float32)
        return nest(flat)

    return build(key)


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------

def _precision(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _conv(x, w, stride, precision):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _bn(x, p, prefix):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.var(x, axis=(0, 1, 2))
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS)
    return y * p[prefix + "/scale"] + p[prefix + "/bias"]


def forward(p: dict, x: jax.Array, cfg: dict) -> jax.Array:
    """Logits, with batch statistics in every batch norm.  Computes in the
    dtype of ``p`` (``x`` is cast to it)."""
    dtype = p["stem/conv/w"].dtype
    prec = _precision(dtype)
    x = x.astype(dtype)
    x = jax.nn.relu(_bn(_conv(x, p["stem/conv/w"], 1, prec), p, "stem/bn"))
    for name, _, _, stride in block_specs(cfg):
        b = f"blocks/{name}"
        h = jax.nn.relu(_bn(_conv(x, p[b + "/conv1/w"], stride, prec), p, b + "/bn1"))
        h = _bn(_conv(h, p[b + "/conv2/w"], 1, prec), p, b + "/bn2")
        if stride != 1:
            sc = _bn(_conv(x, p[b + "/sc_conv/w"], stride, prec), p, b + "/sc_bn")
        else:
            sc = x
        x = jax.nn.relu(h + sc)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, p["head/w"], precision=prec) + p["head/b"]


def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - gold)


def accuracy(p: dict, x, y, cfg: dict) -> jax.Array:
    return jnp.mean((jnp.argmax(forward(p, x, cfg), axis=-1) == y)
                    .astype(jnp.float32))


# ---------------------------------------------------------------------------
# The federated round
# ---------------------------------------------------------------------------

def floyd_sample(rng: np.random.Generator, n: int, k: int) -> list[int]:
    """A uniform k-subset of range(n), in draw order (Floyd's algorithm)."""
    chosen: set[int] = set()
    out = []
    for j in range(n - k, n):
        t = int(rng.integers(0, j + 1))
        pick = t if t not in chosen else j
        chosen.add(pick)
        out.append(pick)
    return out


def client_seed(seed: int, round_index: int, client: int) -> int:
    ss = np.random.SeedSequence((int(seed), int(round_index), int(client)))
    return int(ss.generate_state(1, np.uint32)[0])


def batch_order(n: int, batch: int, epochs: int, seed: int) -> np.ndarray:
    """(steps, batch) sample indices: one permutation per epoch, cut into
    full batches."""
    rng = np.random.default_rng(seed)
    bs = min(batch, n)
    rows = []
    for _ in range(epochs):
        order = rng.permutation(n)
        rows += [order[s: s + bs] for s in range(0, max(n - bs + 1, 1), bs)]
    return np.stack(rows)


@functools.partial(jax.jit, static_argnames=("cfg_items", "lr", "eps", "half"))
def _local_round(p, mask, xs, ys, *, cfg_items, lr, eps, half):
    """One client's local training: Adam over the leaves whose ``mask`` is 1,
    one step per batch of ``xs``; returns the new leaves and the mean loss
    over the steps (each taken before its step's update)."""
    cfg = dict(cfg_items)
    dtype = p["stem/conv/w"].dtype
    zeros = jax.tree.map(jnp.zeros_like, p)

    def body(carry, batch):
        p, m, v, t = carry
        x, y = batch
        if half:                    # a fault for the comparison's tests
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        loss, g = jax.value_and_grad(
            lambda q: cross_entropy(forward(q, x, cfg), y))(p)
        t = t + 1.0
        bc1, bc2 = 1.0 - ADAM_B1 ** t, 1.0 - ADAM_B2 ** t
        m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
        p = jax.tree.map(
            lambda q, a, b, k: q - (k * lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                    ).astype(dtype),
            p, m, v, mask)
        return (p, m, v, t), loss.astype(jnp.float32)

    (p, _, _, _), losses = jax.lax.scan(
        body, (p, zeros, jax.tree.map(jnp.copy, zeros), jnp.float32(0.0)),
        (xs, ys))
    return p, jnp.mean(losses)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _eval(p, x, y, *, cfg_items):
    return accuracy(p, x, y, dict(cfg_items))


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _grad_norms(p, x, y, *, cfg_items):
    cfg = dict(cfg_items)
    g = jax.grad(lambda q: cross_entropy(forward(q, x, cfg), y))(p)
    return jax.tree.map(jnp.linalg.norm, g)


def _items(cfg: dict) -> tuple:
    """The architecture keys as a hashable static argument."""
    keys = ("stages", "channels", "num_classes", "image_size", "in_channels")
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in keys)


@dataclasses.dataclass
class Federation:
    """The reference federation: clients' data on the host, the global
    weights, the cohort stream.  ``dtype`` is float32 for the reference and
    bfloat16 for the control.  Two faults, for the comparison's own
    readings: ``half_batch`` trains on half of every batch, ``half_cohort``
    averages half of the cohort's clients."""

    cfg: dict
    recipe: dict            # cohort, batch, epochs, lr, eps, seed
    client_x: np.ndarray    # (clients, n, H, W, C)
    client_y: np.ndarray    # (clients, n)
    eval_x: np.ndarray
    eval_y: np.ndarray
    dtype: object = jnp.float32
    half_batch: bool = False
    half_cohort: bool = False

    def run(self, params: dict, groups: list[int]) -> dict:
        """Rounds on ``groups`` from ``params`` (the program's nested dict);
        returns each round's mean client loss and eval accuracy, the weights
        after every round (flat float32 numpy), and the per-leaf gradient
        norms at the start (first client's first batch)."""
        rc, items = self.recipe, _items(self.cfg)
        p = {k: jnp.asarray(v, self.dtype) for k, v in flatten(params).items()}
        rng = np.random.default_rng(rc["seed"])
        n_clients, n = self.client_y.shape
        ex, ey = jnp.asarray(self.eval_x), jnp.asarray(self.eval_y)
        out = {"loss": [], "client_loss": [], "acc": [], "params": [],
               "grad_norms": None}
        for r, group in enumerate(groups):
            mask = {k: jnp.asarray(float(group == FULL or group_of(k, self.cfg) == group),
                                   self.dtype) for k in p}
            picked = floyd_sample(rng, n_clients, rc["cohort"])
            news, losses = [], []
            for c in picked:
                idx = batch_order(n, rc["batch"], rc["epochs"],
                                  client_seed(rc["seed"], r, c))
                xs = jnp.asarray(self.client_x[c][idx])
                ys = jnp.asarray(self.client_y[c][idx])
                if out["grad_norms"] is None:
                    gn = _grad_norms({k: v.astype(jnp.float32) for k, v in p.items()},
                                     xs[0], ys[0], cfg_items=items)
                    out["grad_norms"] = {k: float(v) for k, v in gn.items()}
                q, loss = _local_round(p, mask, xs, ys, cfg_items=items,
                                       lr=rc["lr"], eps=rc["eps"],
                                       half=self.half_batch)
                news.append(q)
                losses.append(float(loss))
            if self.half_cohort:
                news, losses = news[: len(news) // 2], losses[: len(losses) // 2]
            p = {k: (jnp.mean(jnp.stack([q[k] for q in news]).astype(jnp.float32),
                              axis=0).astype(self.dtype) if float(mask[k]) else v)
                 for k, v in p.items()}
            out["loss"].append(float(np.mean(losses)))
            out["client_loss"].append(losses)
            out["acc"].append(float(_eval(p, ex, ey, cfg_items=items)))
            out["params"].append({k: np.asarray(v, np.float32) for k, v in p.items()})
        return out

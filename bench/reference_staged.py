"""The plain reference of ``bench/reference.py``, staged over several chips.

A step whose float32 weights do not fit one chip is computed as a
pipeline of stages with no overlap: the embedding on the first chip,
layer ``l`` of ``L`` on chip ``floor(n * l / L)`` of ``n``, the final norm
and the output head on the last. The forward pass keeps only each
layer's input; the backward pass computes each layer again from it
(``jax.vjp``) and hands the gradient of its input to the chip before.
The mathematics is ``bench/reference.py``'s, through its own
``rms_norm``, ``attention`` and ``mlp`` (and so ``rope`` and
``einsum``), in float32 at ``precision="highest"``; ``prec="fp8"`` is its
float8 control. It imports nothing of the program.

The weights are made from the seed by ``bench/weights.py``'s rule, leaf
``i`` from ``fold_in(key, i)``, and each stacked layer leaf is cut into
its layers, each kept on its layer's chip as a float32 master.
"""
from __future__ import annotations

import json
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from bench import reference as R
from bench import weights

F32 = jnp.float32
STACKED = "periods/"            # leaves with one row per layer


def layer_chip(layer: int, layers: int, chips: int) -> int:
    return chips * layer // layers


def stage_key(name: str) -> str:
    """The name a stage gives a leaf: a layer's norm scale by its norm
    (``ln1``, ``ln2``), any other leaf by the last part of its name."""
    parts = name.split("/")
    return parts[-2] if parts[-2:-1] in (["ln1"], ["ln2"]) else parts[-1]


def pieces(spec, layers: int, chips: int) -> list:
    """``(leaf, layer, chip, name)`` of every piece the stages hold, in
    leaf order; ``layer`` is None for a leaf that is not stacked."""
    out = []
    flat = jax.tree_util.tree_flatten_with_path(spec)[0]
    for i, (path, _) in enumerate(flat):
        name = weights.leaf_name(path)
        if name.startswith(STACKED):
            out.extend((i, l, layer_chip(l, layers, chips), name)
                       for l in range(layers))
        else:
            out.append((i, None, 0 if name == "embed" else chips - 1, name))
    return out


_MAKERS: dict = {}


def make_leaf(spec, key, i: int, device):
    """Leaf ``i`` of ``spec`` made from ``key`` by ``bench/weights.py``'s
    rule, on ``device``, by one jitted call of its own: the program's
    weights, the weights its change is measured from and the reference's
    all come from this one function, so they agree bit for bit."""
    path, leaf = jax.tree_util.tree_flatten_with_path(spec)[0][i]
    name = weights.leaf_name(path)
    k = (i, name, leaf.shape, str(leaf.dtype), device)
    if k not in _MAKERS:
        _MAKERS[k] = jax.jit(lambda key: weights.make_leaf(
            jax.random.fold_in(key, i), name, leaf.shape, leaf.dtype),
            out_shardings=SingleDeviceSharding(device))
    return _MAKERS[k](jax.device_put(key, device))


def stored_pieces(spec, key, devices: list, layers: int):
    """Yield ``((leaf, layer), value)`` of every piece as made from
    ``key``, in the leaf's dtype, on its chip: each leaf is made whole on
    the chip of its first piece (:func:`make_leaf`), then cut."""
    by_leaf: dict = {}
    for i, l, chip, _ in pieces(spec, layers, len(devices)):
        by_leaf.setdefault(i, []).append((l, chip))
    for i in range(len(by_leaf)):
        value = make_leaf(spec, key, i, devices[by_leaf[i][0][1]])
        for l, chip in by_leaf[i]:
            yield (i, l), jax.device_put(
                value if l is None else _row(value, l), devices[chip])
        del value


def _block(cfg: dict, layer: dict, ln: dict, x, prec: str):
    eps = cfg["rms_norm_eps"]
    h = R.rms_norm(x, ln["ln1"], eps)
    x = x + R.attention(cfg, layer, 0, h, prec)
    h = R.rms_norm(x, ln["ln2"], eps)
    return x + R.mlp(layer, 0, h, prec)


def _head_loss(cfg: dict, scale, head, x, targets, prec: str):
    h = R.rms_norm(x, scale, cfg["rms_norm_eps"])
    lg = R.einsum("bsd,dv->bsv", h, head, prec)[..., :cfg["vocab_size"]]
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - tgt[..., 0])


class Stages:
    """The staged step over float32 masters, each seen as it is stored:
    rounded to its leaf's dtype, then computed in float32. The gradient
    of such a rounding is the float32 gradient rounded to the leaf's
    dtype, as ``bench/reference.py`` takes it. One instance per
    configuration and precision (:func:`stages`); its executables are
    compiled for every chip at once, in a pool of threads, on the first
    step at each batch shape."""

    def __init__(self, cfg: dict, names: dict, dtypes: dict, prec: str):
        self.cfg = cfg
        self.names = names
        self.layers = int(cfg["num_hidden_layers"])
        self._exe: dict = {}

        def seen(tree):
            return {k: (seen(v) if isinstance(v, dict) else
                        v.astype(dtypes[k]).astype(F32))
                    for k, v in tree.items()}

        def block(m, x):
            s = seen(m)
            layer = {k: v[None] for k, v in s["layer"].items()}
            return _block(cfg, layer, s["ln"], x, prec)

        def head(m, x, t):
            s = seen(m)
            return _head_loss(cfg, s["scale"], s["lm_head"], x, t, prec)

        def embed(m, t):
            return seen(m)["embed"][t]

        self.embed = jax.jit(embed)
        self.embed_grad = jax.jit(
            lambda m, t, g: jax.vjp(lambda m: embed(m, t), m)[1](g)[0])
        self.block = jax.jit(block)
        self.block_grad = jax.jit(
            lambda m, x, g: jax.vjp(block, m, x)[1](g))
        self.head = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))

    def _layer(self, master: dict, l: int) -> tuple[dict, list]:
        """Layer ``l``'s masters by the names ``bench/reference.py``
        reads, and the piece each came from."""
        m: dict = {"layer": {}, "ln": {}}
        keys = []
        for (i, ll), v in master.items():
            if ll != l:
                continue
            key = stage_key(self.names[i])
            m["ln" if key in ("ln1", "ln2") else "layer"][key] = v
            keys.append((i, key))
        return m, keys

    def _compiled(self, master: dict, devices: list, tokens) -> dict:
        """The step's executables for this batch shape, by ``(stage,
        chip)``: the embedding's on the first chip, the head's on the
        last, a layer's forward and backward on each chip that holds
        layers (its layers share their shapes)."""
        key = (tuple(tokens.shape), tuple(devices))
        if key in self._exe:
            return self._exe[key]
        n, layers = len(devices), self.layers
        d = int(self.cfg["hidden_size"])
        idx = {self.names[i]: (i, l) for i, l in master}

        def on(chip, tree):
            sh = SingleDeviceSharding(devices[chip])
            return jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                               sharding=sh), tree)
        x = jax.ShapeDtypeStruct(tuple(tokens.shape) + (d,), F32)
        t = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
        embed = {"embed": master[idx["embed"]]}
        jobs = {("embed", 0): (self.embed, on(0, (embed, t))),
                ("embed_grad", 0): (self.embed_grad, on(0, (embed, t, x))),
                ("head", n - 1): (self.head, on(n - 1, (
                    {"scale": master[idx["final_norm/scale"]],
                     "lm_head": master[idx["lm_head"]]}, x, t)))}
        for l in range(layers):
            chip = layer_chip(l, layers, n)
            m = self._layer(master, l)[0]
            jobs[("block", chip)] = (self.block, on(chip, (m, x)))
            jobs[("block_grad", chip)] = (self.block_grad,
                                          on(chip, (m, x, x)))
        lowered = {k: fn.lower(*args) for k, (fn, args) in jobs.items()}
        with ThreadPoolExecutor(len(lowered)) as pool:
            exe = dict(zip(lowered, pool.map(lambda lw: lw.compile(),
                                             lowered.values())))
        self._exe[key] = exe
        return exe

    def step(self, master: dict, devices: list, batch: dict, update) -> float:
        """One step: the loss at ``master``; ``update(piece, grad)`` is
        called with each piece's gradient as soon as the backward pass
        has made it, the last use of that piece's master in the step."""
        n, layers = len(devices), self.layers
        first, last = devices[0], devices[-1]
        idx = {self.names[i]: (i, l) for i, l in master}
        e, s, h = idx["embed"], idx["final_norm/scale"], idx["lm_head"]
        exe = self._compiled(master, devices, batch["tokens"])
        tokens = jax.device_put(batch["tokens"], first)
        x = exe["embed", 0]({"embed": master[e]}, tokens)
        inputs = []
        for l in range(layers):
            chip = layer_chip(l, layers, n)
            x = jax.device_put(x, devices[chip])
            inputs.append(x)
            x = exe["block", chip](self._layer(master, l)[0], x)
        loss, (g_head, g) = exe["head", n - 1](
            {"scale": master[s], "lm_head": master[h]},
            jax.device_put(x, last), jax.device_put(batch["targets"], last))
        update(s, g_head["scale"])
        update(h, g_head["lm_head"])
        for l in reversed(range(layers)):
            chip = layer_chip(l, layers, n)
            m, keys = self._layer(master, l)
            g = jax.device_put(g, devices[chip])
            g_m, g = exe["block_grad", chip](m, inputs[l], g)
            inputs[l] = None
            for i, key in keys:
                update((i, l), g_m["ln" if key in ("ln1", "ln2")
                                   else "layer"][key])
        update(e, exe["embed_grad", 0]({"embed": master[e]}, tokens,
                                       jax.device_put(g, first))["embed"])
        return float(loss)


_STAGES: dict = {}


def stages(cfg: dict, names: dict, dtypes: dict, prec: str) -> Stages:
    """The :class:`Stages` of a configuration at a precision, made once
    per process, so that later steps and seeds reuse its executables."""
    key = (json.dumps(cfg, sort_keys=True), prec)
    if key not in _STAGES:
        _STAGES[key] = Stages(cfg, names, dtypes, prec)
    return _STAGES[key]


_row = jax.jit(lambda v, l: v[l])        # one executable for every layer
_to_f32 = jax.jit(lambda v: v.astype(F32))
_update = jax.jit(lambda m, g, lr: m - lr * g, donate_argnums=0)
_sq = jax.jit(lambda m, p: jnp.sum(jnp.square(m - p.astype(F32))))


def sgd_steps(cfg: dict, spec, key, devices: list, batches: list,
              lr: float, prec: str = "f32") -> dict:
    """``bench/reference.py``'s ``sgd_steps`` over the stages: SGD with
    float32 master weights from the weights made from ``key``, over
    ``batches``; each step's loss, and per leaf of ``spec`` (in
    flattened order) the first gradient as the update applied it (the
    master's change after one step over ``lr``) and the master's change
    after the last step."""
    layers = int(cfg["num_hidden_layers"])
    flat = jax.tree_util.tree_flatten_with_path(spec)[0]
    names = {i: weights.leaf_name(p) for i, (p, _) in enumerate(flat)}
    dtypes = {stage_key(names[i]): leaf.dtype
              for i, (_, leaf) in enumerate(flat)}
    master = {k: _to_f32(v) for k, v in stored_pieces(spec, key, devices,
                                                        layers)}
    staged = stages(cfg, names, dtypes, prec)

    def update(k, g):
        master[k] = _update(master[k], g, lr)

    def change_norms() -> list:
        """Per leaf, the norm of the master less the weights it started
        from (made again from ``key``, a piece at a time)."""
        sq = [0.0] * len(flat)
        for k, v in stored_pieces(spec, key, devices, layers):
            sq[k[0]] += float(_sq(master[k], v))
        return [x ** 0.5 for x in sq]

    losses, first = [], None
    for batch in batches:
        losses.append(staged.step(master, devices, batch, update))
        if first is None:
            first = [x / lr for x in change_norms()]
    return {"losses": losses, "grad_norms": first,
            "change_norms": change_norms()}

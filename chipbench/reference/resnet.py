"""Plain reference of the ResNet-50 v1.5 training step: forward, softmax
cross-entropy, backward (``jax.grad`` of the plain forward) and SGD with
momentum, in float32 at ``highest`` precision.  It makes its own parameters
from the seed in the program's tree layout and imports nothing of the
program.  Each bottleneck block is rematerialized in the backward pass so
that a batch of 128 at 224 x 224 fits beside nothing else on one chip.

``quant="int8"`` is the control: every convolution's input, kernel and
incoming gradient are rounded to int8 (per tensor), forward and backward,
the nearest precision below bfloat16.
``half_batch`` and ``frozen`` plant two of the faults a training step can
have: half of the rows left out (the mean taken over the rest), and a step
that returns its state unchanged.
"""

from __future__ import annotations

import functools
import json
import zlib

import jax
import jax.numpy as jnp

from .. import weights

BN_EPS, BN_MOMENTUM = 1e-5, 0.9


def _key(seed, name: str):
    """``seed`` is ``weights.seed_words(--seed)``: an argument of the jitted
    program, so that one program serves every seed."""
    if isinstance(seed, int):
        seed = weights.seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed[0]), seed[1])
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def init(model: dict, seed) -> tuple[dict, dict]:
    """(params, batch_stats) in the layout of models/resnet.py under flax's
    automatic names.  Kernels normal with std 1/sqrt(fan_in); every
    BatchNorm scale 1 + 0.1 n and bias 0.1 n (none starts at zero, so every
    leaf has a gradient at the first step)."""
    params, stats = {}, {}

    def conv(path, kh, kw, cin, cout):
        std = (kh * kw * cin) ** -0.5
        return std * jax.random.normal(_key(seed, path), (kh, kw, cin, cout), jnp.float32)

    def norm(path, c):
        p = {
            "scale": 1.0 + 0.1 * jax.random.normal(_key(seed, path + "/scale"), (c,), jnp.float32),
            "bias": 0.1 * jax.random.normal(_key(seed, path + "/bias"), (c,), jnp.float32),
        }
        return p, {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}

    w = model["width"]
    params["Conv_stem"] = {"kernel": conv("Conv_stem", 7, 7, 3, w)}
    params["BatchNorm_0"], stats["BatchNorm_0"] = norm("BatchNorm_0", w)
    cin, idx = w, 0
    for stage, blocks in enumerate(model["stage_sizes"]):
        f = w * 2 ** stage
        for block in range(blocks):
            name = f"BottleneckBlock_{idx}"
            p, s = {}, {}
            shapes = [(1, 1, cin, f), (3, 3, f, f), (1, 1, f, 4 * f)]
            if cin != 4 * f or (stage > 0 and block == 0):
                shapes.append((1, 1, cin, 4 * f))
            for i, shape in enumerate(shapes):
                p[f"Conv_{i}"] = {"kernel": conv(f"{name}/Conv_{i}", *shape)}
                p[f"BatchNorm_{i}"], s[f"BatchNorm_{i}"] = norm(f"{name}/BatchNorm_{i}", shape[3])
            params[name], stats[name] = p, s
            cin, idx = 4 * f, idx + 1
    classes = model["num_classes"]
    params["Dense_0"] = {
        "kernel": cin ** -0.5 * jax.random.normal(_key(seed, "Dense_0"), (cin, classes), jnp.float32),
        "bias": jnp.zeros((classes,), jnp.float32),
    }
    return params, stats


def make_batch(model: dict, seed) -> dict:
    """The synthetic batch, made on the device: rows that all differ."""
    n, size = model["batch_size"], model["image_size"]
    return {
        "images": jax.random.normal(_key(seed, "images"), (n, size, size, 3), jnp.float32),
        "labels": jax.random.randint(_key(seed, "labels"), (n,), 0, model["num_classes"]),
    }


def _fake_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).clip(-127, 127) * scale


def _conv_f32(x, kernel, stride):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest",
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_int8(x, kernel, stride):
    """A convolution as an int8 training step computes it: input and kernel
    rounded to int8 (per tensor) in the forward pass, and the incoming
    gradient rounded too in the two backward convolutions."""
    return _conv_f32(_fake_int8(x), _fake_int8(kernel), stride)


def _conv_int8_fwd(x, kernel, stride):
    return _conv_int8(x, kernel, stride), (x, kernel)


def _conv_int8_bwd(stride, saved, dy):
    x, kernel = saved
    _, vjp = jax.vjp(lambda a, b: _conv_f32(a, b, stride), _fake_int8(x), _fake_int8(kernel))
    return vjp(_fake_int8(dy))


_conv_int8.defvjp(_conv_int8_fwd, _conv_int8_bwd)


def _conv(x, kernel, stride, quant):
    return _conv_int8(x, kernel, stride) if quant == "int8" else _conv_f32(x, kernel, stride)


def _norm(x, p):
    """Training-mode batch normalisation; returns (y, batch mean, batch var)."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    return y, mean, var


def _block(p, x, stride, quant):
    new = {}
    y, *new["BatchNorm_0"] = _norm(_conv(x, p["Conv_0"]["kernel"], 1, quant), p["BatchNorm_0"])
    y, *new["BatchNorm_1"] = _norm(_conv(jax.nn.relu(y), p["Conv_1"]["kernel"], stride, quant), p["BatchNorm_1"])
    y, *new["BatchNorm_2"] = _norm(_conv(jax.nn.relu(y), p["Conv_2"]["kernel"], 1, quant), p["BatchNorm_2"])
    if "Conv_3" in p:
        x, *new["BatchNorm_3"] = _norm(_conv(x, p["Conv_3"]["kernel"], stride, quant), p["BatchNorm_3"])
    return jax.nn.relu(y + x), new


def loss_fn(params, images, labels, model: dict, quant=None):
    """Mean softmax cross-entropy of the batch, and each BatchNorm's batch
    statistics (for the running averages)."""
    new = {}
    x, *new["BatchNorm_0"] = _norm(_conv(images, params["Conv_stem"]["kernel"], 2, quant), params["BatchNorm_0"])
    x = jax.lax.reduce_window(
        jax.nn.relu(x), -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    idx = 0
    for stage, blocks in enumerate(model["stage_sizes"]):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            name = f"BottleneckBlock_{idx}"
            run = jax.checkpoint(functools.partial(_block, stride=stride, quant=quant))
            x, new[name] = run(params[name], x)
            idx += 1
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.matmul(x, params["Dense_0"]["kernel"], precision="highest") + params["Dense_0"]["bias"]
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, new


def train_step(params, momentum, batch, model: dict, quant=None, half_batch=False, frozen=False):
    """One step of SGD with momentum (optax.sgd's: m = g + mu m; p -= lr m).
    Returns (params, momentum, loss, gradients)."""
    images, labels = batch["images"], batch["labels"]
    if half_batch:
        images, labels = images[: images.shape[0] // 2], labels[: labels.shape[0] // 2]
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, images, labels, model, quant)
    if frozen:
        return params, momentum, loss, grads
    lr, mu = model["learning_rate"], model["momentum"]
    momentum = jax.tree.map(lambda m, g: g + mu * m, momentum, grads)
    params = jax.tree.map(lambda p, m: p - lr * m, params, momentum)
    return params, momentum, loss, grads


@functools.lru_cache(maxsize=8)
def _seeded_program(model_json: str):
    model = json.loads(model_json)
    return jax.jit(lambda words: (*init(model, words), make_batch(model, words)))


def seeded(model: dict, seed: int) -> tuple[dict, dict, dict]:
    """(params, batch_stats, batch) of ``--seed``, made on the device by one program
    that is the same for every seed."""
    return _seeded_program(json.dumps(model, sort_keys=True))(weights.seed_words(seed))


@functools.lru_cache(maxsize=8)
def _step_program(model_json: str, fault: tuple):
    return jax.jit(functools.partial(train_step, model=json.loads(model_json), **dict(fault)))


def follow(model: dict, seed: int, steps: int = 3, **fault) -> dict:
    """The first ``steps`` steps from the seed: each step's loss, the first
    gradient's norm leaf by leaf, and the norm of each leaf's change after
    the last step."""
    params0, _, batch = seeded(model, seed)
    step = _step_program(json.dumps(model, sort_keys=True), tuple(sorted(fault.items())))
    params, momentum = params0, jax.tree.map(jnp.zeros_like, params0)
    losses, grad_norms = [], None
    for i in range(steps):
        params, momentum, loss, grads = step(params, momentum, batch)
        losses.append(float(loss))
        if i == 0:
            grad_norms = leaf_norms(grads)
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change_norms(params, params0)}


def leaf_norms(tree) -> dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in t])([x for _, x in flat])
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): float(n)
        for (path, _), n in zip(flat, norms)
    }


def change_norms(new, old) -> dict[str, float]:
    """Norm of each leaf's change, in one program."""
    return leaf_norms(jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))(new, old))


def compare(program: dict, reference: dict, worst: list | None = None) -> dict[str, float]:
    """The numbers a training cell compares: each step's loss as a relative
    gap; for the first gradient and for the change after the last step the
    gap of norms leaf by leaf, measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger: the worst leaf's
    (``*_norm_gap``) and the median leaf's (``*_median_gap``, steady from
    seed to seed where the worst leaf's is one small leaf's noise).  Leaves
    whose reference gradient is under a thousandth of the median leaf's are
    left out of the change (they move by round-off alone); ``unmoved_leaves``
    counts the others that the program did not move at all.  ``worst``, if
    given, gets a line about the five widest leaves of each."""
    from statistics import median

    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss{i + 1}_gap"] = abs(a - b) / abs(b)
    g_ref = reference["grad_norms"]
    g_med = median(g_ref.values())
    g_gap = {k: abs(program["grad_norms"][k] - g_ref[k]) / max(g_ref[k], g_med) for k in g_ref}
    out["grad_norm_gap"], out["grad_median_gap"] = max(g_gap.values()), median(g_gap.values())
    c_ref = reference["change_norms"]
    moved = [k for k in c_ref if g_ref[k] >= 1e-3 * g_med]
    c_med = median(c_ref[k] for k in moved)
    c_gap = {k: abs(program["change_norms"][k] - c_ref[k]) / max(c_ref[k], c_med) for k in moved}
    out["change_norm_gap"], out["change_median_gap"] = max(c_gap.values()), median(c_gap.values())
    # A leaf that the reference moves and the program has left where it was
    # (its change under a hundredth of the reference's): an exact count.  The
    # worst leaf's gap reads 1 for such a leaf, inside bfloat16's own noise.
    out["unmoved_leaves"] = sum(1 for k in moved if program["change_norms"][k] < 0.01 * c_ref[k])
    if worst is not None:
        for name, gaps, ref, prog in (("grad", g_gap, g_ref, program["grad_norms"]),
                                      ("change", c_gap, c_ref, program["change_norms"])):
            top = sorted(gaps, key=gaps.get, reverse=True)[:5]
            left_out = len(ref) - len(moved) if name == "change" else 0
            worst.append(f"{name}: median leaf {median(ref.values()):.3e}, left out {left_out}; "
                         + "; ".join(f"{k} gap {gaps[k]:.3f} (program {prog[k]:.3e}, reference {ref[k]:.3e})" for k in top))
    return out

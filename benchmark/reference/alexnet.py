"""Plain reference of one-tower AlexNet (``configs/alexnet.json``).

The forward pass of Krizhevsky et al. 2012 as the configuration file's
layer table states it, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, evaluation mode (dropout is
the identity; the program uses inverted dropout, so nothing is rescaled
at evaluation). Nothing is imported from the program; it takes the
program's weights and the raw uint8 images.

    x      = (centre crop 227 of the 256x256 image / 255 - 0.5) / 0.5
    conv   : y = act(x * W + b), act(v) = log(1 + e^v)  (the upstream
             sample's soft ReLU; see the configuration's "assumed")
    lrn    : y_c = x_c / (k + alpha * sum_{c' in window n of c} x_c'^2)^beta
    pool   : max over 3x3 windows, stride 2
    fc     : y = act(x W + b) on the (H, W, C)-flattened input
    loss   = mean over images of -log softmax(fc8)[label]
"""

import functools

import jax
import jax.numpy as jnp
import numpy


def weighted_layers(model):
    return [l for l in model["layers"] if l["type"] in ("conv", "fc")]


def from_program(units, model):
    """``units``: [(kind, {name: array})] of the program's forward
    units in order; -> [{"w", "b"}] of the weighted layers, each conv
    filter bank reshaped to HWIO. Shapes are checked against the
    configuration's layer table."""
    units = [(k, p) for k, p in units if p]
    layers = weighted_layers(model)
    if len(units) != len(layers):
        raise ValueError("program has %d weighted layers, the "
                         "configuration %d" % (len(units), len(layers)))
    tree = []
    h = w = model["crop"]
    c = model["channels"]
    table = iter(model["layers"])
    for (kind, params), layer in zip(units, layers):
        for skipped in table:       # walk pools up to this layer
            if skipped is layer:
                break
            if skipped["type"] == "pool":
                h = (h - skipped["kernel"]) // skipped["stride"] + 1
                w = (w - skipped["kernel"]) // skipped["stride"] + 1
        weights = numpy.asarray(params["weights"], numpy.float32)
        if layer["type"] == "conv":
            k, n = layer["kernel"], layer["filters"]
            want = (n, k * k * c)
            if weights.shape == want:   # (filters, ky*kx*C) -> HWIO
                weights = weights.reshape(n, k, k, c).transpose(1, 2, 3, 0)
            h = (h + 2 * layer["pad"] - k) // layer["stride"] + 1
            w = (w + 2 * layer["pad"] - k) // layer["stride"] + 1
            c = n
        else:
            want = (h * w * c, layer["units"])
            h, w, c = 1, 1, layer["units"]
        if tuple(params["weights"].shape) != want:
            raise ValueError(
                "%s (%s): weights %r, the configuration says %r"
                % (layer["name"], kind, params["weights"].shape, want))
        tree.append({"w": jnp.asarray(weights),
                     "b": jnp.asarray(params["bias"], jnp.float32)})
    return tree


def soft_relu(v):
    return jnp.logaddexp(v, 0.0)


def lrn(x, layer):
    """Cross-map local response normalization over a window of ``n``
    channels centred on each channel (zero beyond the ends)."""
    n = layer["n"]
    lo = (n - 1) // 2
    sq = jnp.pad(x * x, ((0, 0), (0, 0), (0, 0), (lo, n - 1 - lo)))
    window = sum(sq[..., i:i + x.shape[-1]] for i in range(n))
    return x / (layer["k"] + layer["alpha"] * window) ** layer["beta"]


def forward(tree, images, model):
    """uint8 (B, 256, 256, 3) -> logits (B, n_classes)."""
    off = (model["image"] - model["crop"]) // 2
    x = images[:, off:off + model["crop"], off:off + model["crop"], :]
    x = (x.astype(jnp.float32) / 255.0 - 0.5) / 0.5
    params = iter(tree)
    for layer in model["layers"]:
        kind = layer["type"]
        if kind == "conv":
            p = next(params)
            x = jax.lax.conv_general_dilated(
                x, p["w"], (layer["stride"],) * 2,
                [(layer["pad"],) * 2] * 2,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = soft_relu(x + p["b"])
        elif kind == "lrn":
            x = lrn(x, layer)
        elif kind == "pool":
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max,
                (1, layer["kernel"], layer["kernel"], 1),
                (1, layer["stride"], layer["stride"], 1), "VALID")
        elif kind == "fc":
            p = next(params)
            x = x.reshape(x.shape[0], -1) @ p["w"] + p["b"]
            if not layer.get("softmax"):
                x = soft_relu(x)
        # dropout: identity at evaluation
    return x


@functools.partial(jax.jit, static_argnames=("model_json",))
def _loss(tree, images, labels, model_json):
    import json
    model = json.loads(model_json)
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(forward(tree, images, model), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def loss(tree, batch, model):
    """Mean cross-entropy of ``batch`` = (uint8 images, labels)."""
    import json
    images, labels = batch
    return float(_loss(tree, jnp.asarray(images),
                       jnp.asarray(labels, jnp.int32),
                       json.dumps(model, sort_keys=True)))

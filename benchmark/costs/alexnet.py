"""Operations of one-tower AlexNet (``configs/alexnet.json``), computed
from the layer table in the configuration file.

A multiply-accumulate is 2 FLOP. Training needs the forward product,
the weight gradient and the input gradient of every weighted layer —
3 x the forward — except that the first layer's input gradient is never
needed (nothing trains below the image), so conv1 counts 2 x.
"""


def out_size(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def layer_macs(model):
    """[(layer name, multiply-accumulates per image)] of the weighted
    layers, walking the configuration's layer table from the crop."""
    h = w = model["crop"]
    c = model["channels"]
    out = []
    for layer in model["layers"]:
        kind = layer["type"]
        if kind == "conv":
            k, s, p = layer["kernel"], layer["stride"], layer["pad"]
            h, w = out_size(h, k, s, p), out_size(w, k, s, p)
            out.append((layer["name"],
                        h * w * layer["filters"] * k * k * c))
            c = layer["filters"]
        elif kind == "pool":
            k, s = layer["kernel"], layer["stride"]
            h, w = out_size(h, k, s, 0), out_size(w, k, s, 0)
        elif kind == "fc":
            fan_in = h * w * c
            out.append((layer["name"], fan_in * layer["units"]))
            h, w, c = 1, 1, layer["units"]
    return out


def train_flops_per_sample(model, traffic=None):
    """A sample is one image."""
    macs = layer_macs(model)
    return 6.0 * sum(m for _, m in macs) - 2.0 * macs[0][1]

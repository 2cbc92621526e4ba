"""Plain float32 reference of AlexNet for 32x32 images, split for SCALA
(SCALA paper, Appendix E, Fig. 6; split point s2 of Appendix H).

Five 3x3 stride-1 "same" convolutions (channels 64, 192, 384, 256, 256,
ReLU, 2x2 max pools after the first, second and fifth), two ReLU fully
connected layers of 4096 and a linear classifier. The first
``client_convs`` convolutions run on each client, the rest on the
server. The flattening before the first fully connected layer reads
the (4, 4, 256) feature map in height, width, channel order. Imports
nothing of the system under test.

Canonical layout (what ``init_weights`` makes)::

    {"client": {"conv0": {"w", "b"}, ...},
     "server": {"conv2": ..., "fc0": ..., "fc1": ..., "head": {"w", "b"}}}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
INPUT = "x"
POOL_AFTER = (True, True, False, False, True)


def _shapes(config):
    s = config["sizes"]
    chans, fcs = s["conv_channels"], s["fc_widths"]
    hw, cin = s["image_hw"], s["image_channels"]
    convs, c = [], cin
    for co in chans:
        convs.append((3, 3, c, co))
        c = co
    for pool in POOL_AFTER:
        hw //= 2 if pool else 1
    dense, din = [], hw * hw * chans[-1]
    for f in fcs:
        dense.append((din, f))
        din = f
    dense.append((din, s["vocab_size"]))
    return convs, dense


def init_weights(config, key):
    convs, dense = _shapes(config)
    keys = jax.random.split(key, 2 * (len(convs) + len(dense)))
    layers = {}
    for i, sh in enumerate(convs):
        fan = sh[0] * sh[1] * sh[2]
        layers[f"conv{i}"] = {
            "w": jax.random.normal(keys[2 * i], sh) * fan ** -0.5,
            "b": jax.random.normal(keys[2 * i + 1], sh[-1:]) * 0.01}
    for j, sh in enumerate(dense):
        k = 2 * (len(convs) + j)
        name = f"fc{j}" if j < len(dense) - 1 else "head"
        layers[name] = {"w": jax.random.normal(keys[k], sh) * sh[0] ** -0.5,
                        "b": jax.random.normal(keys[k + 1], sh[-1:]) * 0.01}
    n = config["sizes"]["client_convs"]
    client = {f"conv{i}": layers.pop(f"conv{i}") for i in range(n)}
    return {"client": client, "server": layers}


# ---------------------------------------------------------------------------
# the program's parameter layout (models/alexnet.py) <-> canonical
# ---------------------------------------------------------------------------


def _n(config):
    sz = config["sizes"]
    return sz["client_convs"], len(sz["conv_channels"])


def to_program(config, canon, slots: int):
    n, nconv = _n(config)
    c, s = canon["client"], canon["server"]
    client = {"convs": [c[f"conv{i}"] for i in range(n)]}
    client = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (slots,) + a.shape), client)
    nfc = len(config["sizes"]["fc_widths"])
    server = {"convs": [s[f"conv{i}"] for i in range(n, nconv)],
              "fcs": [s[f"fc{j}"] for j in range(nfc)], "head": s["head"]}
    return {"client": client, "server": server}


def from_program(config, params):
    n, _ = _n(config)
    c = jax.tree.map(lambda a: a[0], params["client"])
    s = params["server"]
    server = {f"conv{n + i}": p for i, p in enumerate(s["convs"])}
    server.update({f"fc{j}": p for j, p in enumerate(s["fcs"])})
    server["head"] = s["head"]
    return {"client": {f"conv{i}": p for i, p in enumerate(c["convs"])},
            "server": server}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _conv(p, x, pool, q):
    y = jax.lax.conv_general_dilated(
        q(x), q(p["w"]), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    y = jax.nn.relu(y + p["b"])
    if pool:
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    return y


def _dense(p, x, q):
    return jnp.dot(q(x), q(p["w"]), precision=HI) + p["b"]


def client_forward(wc, x, config, q):
    for i in range(len(wc)):
        x = _conv(wc[f"conv{i}"], x, POOL_AFTER[i], q)
    return x


def server_logits(ws, x, config, q):
    n, nconv = _n(config)
    for i in range(n, nconv):
        x = _conv(ws[f"conv{i}"], x, POOL_AFTER[i], q)
    x = x.reshape(x.shape[0], -1)
    for j in range(len(config["sizes"]["fc_widths"])):
        x = jax.nn.relu(_dense(ws[f"fc{j}"], x, q))
    return _dense(ws["head"], x, q)


# ---------------------------------------------------------------------------
# matmul FLOPs the round requires
# ---------------------------------------------------------------------------


def round_flops(config, expect) -> float:
    """Convolution and matmul FLOPs one round requires: participating
    samples only.

    Per sample and local step, with F = 2 x multiply-adds of a layer's
    forward (a 3x3 "same" convolution: 2 * H * W * 9 * cin * cout at its
    input resolution):

    * client convolutions: forward, weight gradient and input gradient
      (3 F); the first one's input gradient (towards the image) is not
      needed (2 F);
    * server layers: forward, weight gradient under eq. 14, input
      gradients under eq. 14 and eq. 15 (4 F); the first server layer's
      eq. 14 input gradient is not needed (3 F).

    Bias, ReLU, pooling and the loss are not counted.
    """
    convs, dense = _shapes(config)
    s = config["sizes"]
    hw, n = s["image_hw"], s["client_convs"]
    fwd = []
    for i, (kh, kw, ci, co) in enumerate(convs):
        fwd.append(2 * hw * hw * kh * kw * ci * co)
        hw //= 2 if POOL_AFTER[i] else 1
    fwd += [2 * a * b for a, b in dense]
    mult = [3] * n + [4] * (len(fwd) - n)
    mult[0] -= 1
    mult[n] -= 1
    per_sample = sum(f * k for f, k in zip(fwd, mult))
    return float(expect["rows_per_step"] * expect["local_iters"] * per_sample)

"""Plain float32 reference of the model zoo's bottleneck ResNet v1
(He et al. 2015, Table 1): forward pass and mean softmax cross-entropy in
straightforward ``jax.numpy``, every product at "highest" precision,
BatchNorm on the statistics of the batch (training mode, eps 1e-5,
biased variance), no framework code.

Parameters come by the names ``net.collect_params()`` gives them, without
the network's own prefix: ``conv0_weight``, ``stage2_batchnorm3_gamma``,
``dense0_bias``. Inside a stage the model zoo numbers convolutions and
norms in order of creation: a block is conv (1x1, stride, bias), norm,
conv (3x3), norm, conv (1x1, bias), norm, and a stage's first block then
adds its projection shortcut, conv (1x1, stride), norm.
"""
from __future__ import annotations

BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def forward_loss(params, x, labels, depth=50):
    """``x (N, 3, H, W)``, integer ``labels (N,)`` -> scalar mean loss."""
    import jax
    import jax.numpy as jnp
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    x = jnp.asarray(x, jnp.float32)

    def conv(x, name, stride=1, pad=0):
        y = jax.lax.conv_general_dilated(
            x, p[name + "_weight"], (stride, stride),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        bias = p.get(name + "_bias")
        return y if bias is None else y + bias[None, :, None, None]

    def norm(x, name):
        mean = jnp.mean(x, (0, 2, 3), keepdims=True)
        var = jnp.mean((x - mean) ** 2, (0, 2, 3), keepdims=True)
        g = p[name + "_gamma"][None, :, None, None]
        b = p[name + "_beta"][None, :, None, None]
        return (x - mean) / jnp.sqrt(var + 1e-5) * g + b

    with jax.default_matmul_precision("highest"):
        x = jax.nn.relu(norm(conv(x, "conv0", 2, 3), "batchnorm0"))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
        for stage, n_blocks in enumerate(BLOCKS[depth], start=1):
            names = iter(range(10 ** 6))
            for block in range(n_blocks):
                stride = 2 if (stage > 1 and block == 0) else 1

                def layer(x, stride=1, pad=0, act=True):
                    i = next(names)
                    x = norm(conv(x, "stage%d_conv%d" % (stage, i),
                                  stride, pad),
                             "stage%d_batchnorm%d" % (stage, i))
                    return jax.nn.relu(x) if act else x

                y = layer(x, stride)
                y = layer(y, 1, 1)
                y = layer(y, act=False)
                if block == 0:
                    x = layer(x, stride, act=False)
                x = jax.nn.relu(x + y)
        x = jnp.mean(x, (2, 3))
        logits = x @ p["dense0_weight"].T + p["dense0_bias"]
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(
            logp, jnp.asarray(labels, jnp.int32)[:, None], 1)
        return -jnp.mean(picked)

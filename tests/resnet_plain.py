"""A plain reference of residual CUTIE graphs for the tests: seeded trits
and per-channel scales, and straightforward `jax.numpy` in float32 at
``HIGHEST`` — no kernels, no packing, no plan.

    y_i = scale_i * conv(a_{i-1}, T_i) + S(a_k),    a_i = ternarize(y_i)

``S`` is the identity where the shapes match, otherwise every 2nd row and
column from the top-left with zero channels appended.  A strided conv
keeps every ``stride``-th output row and column from the first.
"""
import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seeded_weights(graph, seed: int, weight_density: float = 0.58,
                   input_density: float = 0.6, gain=(0.7, 1.1)):
    """``{"conv": [(trits HWIO int8, scale f32)], "fc": (trits, scale)}``:
    each scale a gain over sqrt(fan-in x densities), so pre-threshold
    values have a standard deviation near 1."""
    rng = np.random.RandomState(seed)

    def layer(shape, fan_in):
        u = rng.uniform(size=shape)
        t = np.where(u < weight_density / 2, -1, np.where(u < weight_density, 1, 0))
        g = rng.uniform(*gain, size=shape[-1])
        s = g / np.sqrt(fan_in * weight_density * input_density)
        return jnp.asarray(t.astype(np.int8)), jnp.asarray(s.astype(np.float32))

    out = {"conv": [], "fc": None}
    for l in graph.layers:
        if l.kind == "conv2d":
            out["conv"].append(layer((*l.kernel, l.c_in, l.c_out),
                                     l.kernel[0] * l.kernel[1] * l.c_in))
        elif l.kind == "fc":
            out["fc"] = layer((l.c_in, l.c_out), l.c_in)
    return out


def forward(graph, weights, x):
    """(logits, {conv index: its ternary output map})."""
    thr = jnp.float32(graph.act_threshold)
    maps, ci = {}, 0
    x = x.astype(jnp.float32)
    for i, l in enumerate(graph.layers):
        if l.kind == "conv2d":
            t, scale = weights["conv"][ci]
            ci += 1
            kh, kw = l.kernel
            pad = (((kh - 1) // 2, kh // 2), ((kw - 1) // 2, kw // 2))
            y = jax.lax.conv_general_dilated(
                x, t.astype(jnp.float32), (l.stride, l.stride), pad,
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
            y = y * scale
            if l.shortcut is not None:
                a = maps[l.shortcut]
                s = a.shape[1] // y.shape[1]
                a = a[:, ::s, ::s, :]
                y = y + jnp.pad(a, ((0, 0),) * 3 + ((0, y.shape[-1] - a.shape[-1]),))
            x = maps[i] = jnp.where(jnp.abs(y) > thr, jnp.sign(y), 0.0)
        elif l.kind == "pool":
            n, h, w, c = x.shape
            x = x.reshape(n, h // l.window, l.window, w // l.window, l.window, c).max((2, 4))
        elif l.kind == "global_pool":
            x = x.mean(axis=(1, 2))
        elif l.kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif l.kind == "fc":
            t, scale = weights["fc"]
            x = jnp.matmul(x, t.astype(jnp.float32), precision=HIGHEST) * scale
    return x, maps

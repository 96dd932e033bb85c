"""Mean host time per tick that the benchmark's client spends moving the
tick's logits to the host (`jax.device_get` of the tick's result)."""
import numpy as np


def read(run):
    if not len(run.window.fetch_s):
        return None
    return float(np.mean(run.window.fetch_s)) * 1e3

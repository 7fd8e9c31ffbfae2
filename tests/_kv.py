"""Read a KV cache in token-major order, whatever layout stores it.

The cache stores keys and values head-major, ``(..., B, H_kv, T, D)``;
``token_major`` gives every such leaf as ``(..., B, T, H_kv, D)``, the
order in which entries are written and positions (``pos``, ``(..., B,
T)``) are kept, and every other leaf as it is, all as numpy arrays.
"""
import jax
import numpy as np

HEAD_MAJOR = ("k", "v", "xk", "xv")


def token_major(cache):
    def leaf(path, x):
        x = np.asarray(x)
        if str(getattr(path[-1], "key", "")) in HEAD_MAJOR:
            return np.swapaxes(x, -3, -2)
        return x
    return jax.tree_util.tree_map_with_path(leaf, cache)

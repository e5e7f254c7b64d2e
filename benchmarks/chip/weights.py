"""The benchmark's own random weights, made on the device from the seed.

The program's parameter tree gives only the layout (names, shapes, dtypes:
``model.abstract()``); every value comes from here, by the leaf's name, in
one jitted call and in the dtype it is served in. The same seed gives the
same weights, and the reference reads the same tree, so nothing it compares
against was made by the program.

Rules by leaf name (fan-in is the input width of the projection):

* ``embed``: N(0, 0.02), the configs' ``initializer_range``;
* projections: N(0, 1/fan_in);
* norm and skip scales (``ln*``, ``*norm``, ``d_skip``): 1 + 0.1 N(0, 1),
  so that a norm applied with the wrong weight shows;
* Mamba2 ``conv_*``: U(-1/sqrt(width), 1/sqrt(width)), PyTorch's conv init;
  ``a_log``: log U[1, 16]; ``dt_bias``: softplus^-1 of a step log-uniform in
  [1e-3, 1e-1] (the published Mamba2 init).

A configuration file may add ``"init": {leaf: {"std": s}}`` for a leaf these
rules do not know; an unknown leaf is an error, not a guess.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# projections: leaf name -> axes (counted from the end, after the layer
# axis) that are the input side
FAN_IN = {
    "wq": (-3,), "wk": (-3,), "wv": (-3,), "wo": (-3, -2),
    "w_gate": (-2,), "w_up": (-2,), "w_down": (-2,),
    "w_z": (-2,), "w_x": (-2,), "w_b": (-2,), "w_c": (-2,), "w_dt": (-2,),
    "w_out": (-2,), "unembed": (-2,), "vision_proj": (-2,),
}
SCALES = ("ln", "ln1", "ln2", "final_norm", "norm", "d_skip", "q_norm",
          "k_norm")


def _leaf(name: str, shape, dtype, key, extra: dict):
    if name in extra:
        v = jax.random.normal(key, shape, jnp.float32) * extra[name]["std"]
    elif name == "embed":
        v = jax.random.normal(key, shape, jnp.float32) * 0.02
    elif name in FAN_IN:
        fan = int(np.prod([shape[a] for a in FAN_IN[name]]))
        v = jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan)
    elif name in SCALES:
        v = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name.startswith("conv_"):
        b = 1.0 / np.sqrt(shape[-2])
        v = jax.random.uniform(key, shape, jnp.float32, -b, b)
    elif name == "a_log":
        v = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        lo, hi = np.log(1e-3), np.log(1e-1)
        step = jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape))
        v = step + jnp.log(-jnp.expm1(-step))
    else:
        raise KeyError(f"no init rule for weight leaf {name!r}; add one under "
                       f"'init' in the configuration file")
    return v.astype(dtype)


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may pass 32 bits)."""
    return jax.random.key(int(np.random.default_rng(int(seed) % 2**63)
                              .integers(0, 2**31)))


def make_weights(abstract, seed: int, extra: dict | None = None):
    """Random weights with the layout of ``abstract`` (a tree of
    ShapeDtypeStruct), in one jitted call on the default device."""
    extra = extra or {}
    paths = jax.tree_util.tree_flatten_with_path(abstract)[0]
    treedef = jax.tree.structure(abstract)

    def build(key):
        out = []
        for path, sds in paths:
            name = str(getattr(path[-1], "key", path[-1]))
            tag = zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31
            out.append(_leaf(name, sds.shape, sds.dtype,
                             jax.random.fold_in(key, tag), extra))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(jax_key(seed))

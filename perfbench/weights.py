"""The benchmark's own weights: made on the device from --seed in one jitted
call, in the type the configuration serves them in.  The program under test
and the plain reference are both given these values; neither makes any."""

import jax
import jax.numpy as jnp

INIT_STD = 0.02     # the published initializer_range of the BERT configs


def seed_key(seed):
    """A PRNG key for any whole-number seed, also one over 2**32."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def make_weights(shapes, seed, dtype, device=None):
    """``shapes``: name -> (shape, 'normal' | 'zeros' | 'ones'), as a
    reference's ``param_shapes`` gives them.  Returns name -> array."""
    dtype = jnp.dtype(dtype)

    def make(key):
        out = {}
        for n, (name, (shape, init)) in enumerate(shapes.items()):
            if init == "normal":
                w = INIT_STD * jax.random.normal(jax.random.fold_in(key, n),
                                                 shape, jnp.float32)
            elif init in ("zeros", "ones"):
                w = jnp.full(shape, float(init == "ones"), jnp.float32)
            else:
                raise ValueError(f"unknown init {init!r} for {name}")
            out[name] = w.astype(dtype)
        return out

    with jax.default_device(device):
        return jax.jit(make)(seed_key(seed))

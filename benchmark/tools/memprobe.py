#!/usr/bin/env python3
"""Does ``memory_stats()["peak_bytes_in_use"]`` on this backend count a
program's temporaries?  Runs one jitted program whose only large memory
is a 2 GiB intermediate and prints the statistics before and after.
(Builder's probe, PR 23: the answer decides what ``memory_peak_bytes``
means.)"""

import json

import jax
import jax.numpy as jnp


def main():
    dev = jax.local_devices()[0]
    print("before", json.dumps(dev.memory_stats()))

    @jax.jit
    def f(x):
        big = jnp.broadcast_to(x[:, None], (x.shape[0], 16384))  # 2 GiB f32
        big = jnp.sin(big) * 2.0 + jnp.cos(big[::-1])
        return big.sum(axis=1)

    x = jnp.arange(32768, dtype=jnp.float32)
    f(x).block_until_ready()
    print("after", json.dumps(dev.memory_stats()))
    compiled = f.lower(x).compile()
    m = compiled.memory_analysis()
    print("analysis temp=%d args=%d out=%d" % (
        m.temp_size_in_bytes, m.argument_size_in_bytes,
        m.output_size_in_bytes))


if __name__ == "__main__":
    main()

"""Run by hand on the chip: does a narrowing cast that is widened again
survive the compiler?  Prints, for a matmul of N(0, 1/64) operands, the
relative error against float32 at ``highest`` of (a) bfloat16 operands,
(b) operands cast to ``float8_e4m3fn`` and back (how this benchmark's
control first rounded), (c) ``dense_lm._round_operand`` (how it rounds
now), and the same casts on their own."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax                # noqa: E402
import jax.numpy as jnp   # noqa: E402

from benchmark.models import dense_lm as M   # noqa: E402


def cast_pair(x):
    r = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (x + jax.lax.stop_gradient(r - x)).astype(jnp.bfloat16)


def scaled(x):
    return M._round_operand(x, 4, 3).astype(jnp.bfloat16)


def main():
    a = 0.0156 * jax.random.normal(jax.random.PRNGKey(0), (2048, 2048))
    b = 0.0156 * jax.random.normal(jax.random.PRNGKey(1), (2048, 2048))
    exact = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def rel(y, ref=exact):
        return float(jnp.linalg.norm(y.astype(jnp.float32) - ref) /
                     jnp.linalg.norm(ref))
    for name, rounded in (("bfloat16", lambda x: x.astype(jnp.bfloat16)),
                          ("cast_pair_e4m3fn", cast_pair),
                          ("scaled_reduce_precision_e4m3", scaled)):
        dot = jax.jit(lambda p, q, f=rounded: jnp.matmul(
            f(p), f(q), preferred_element_type=jnp.float32))
        alone = jax.jit(rounded)
        print(name, "matmul_error", rel(dot(a, b)),
              "operand_error", rel(alone(a), a), flush=True)
    print("platform", jax.devices()[0].platform)


if __name__ == "__main__":
    main()

"""Micro-bench the verify kernel's building blocks on the live backend.

Times each component as a lax.scan chain (so per-dispatch overhead
amortizes) and reports ns per op per lane — the number to push down.
"""

import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from bitcoinconsensus_tpu.ops import limbs as L
from bitcoinconsensus_tpu.ops import curve as C

B = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
REPS = 50


def _force(out):
    """Materialize on host: fetch one element of every leaf."""
    return [np.asarray(jnp.ravel(x)[:1]) for x in jax.tree.leaves(out)]


def bench(name, fn, *args, reps=REPS):
    jfn = jax.jit(fn)
    _force(jfn(*args))  # compile + warm
    t0 = time.perf_counter()
    _force(jfn(*args))
    base = time.perf_counter() - t0  # includes the fixed readback
    t0 = time.perf_counter()
    _force(jfn(*args))
    dt = min(base, time.perf_counter() - t0)
    per = dt / reps
    print(
        f"{name:28s} {dt*1e3:8.1f} ms total  {per*1e6:9.2f} us/step "
        f"{per/B*1e9:8.1f} ns/lane/step"
    )
    return per


def main():
    rng = np.random.default_rng(7)
    a = rng.integers(0, L.MASK, size=(L.NLIMB, B), dtype=np.int32)
    b = rng.integers(0, L.MASK, size=(L.NLIMB, B), dtype=np.int32)

    def chain_mul(a, b):
        def body(x, _):
            return L.fe_mul(x, b), None
        out, _ = lax.scan(body, a, None, length=REPS)
        return out

    def chain_conv_only(a, b):
        def body(x, _):
            acc, _bounds = L._conv_rows(x[: L.NLIMB], b, L.W2, L.W2)
            return acc[: 2 * L.NLIMB - 1], None
        x0 = jnp.concatenate([a, jnp.zeros((L.NLIMB - 1, B), jnp.int32)], 0)
        out, _ = lax.scan(lambda x, _: (jnp.concatenate(
            [L._conv_rows(x[:L.NLIMB] & L.MASK, b, L.W2, L.W2)[0][:L.NLIMB],
             jnp.zeros((L.NLIMB - 1, B), jnp.int32)], 0), None), x0, None,
            length=REPS)
        return out

    def chain_sqr(a):
        def body(x, _):
            return L.fe_sqr(x), None
        out, _ = lax.scan(body, a, None, length=REPS)
        return out

    def chain_add(a, b):
        def body(x, _):
            return L.fe_add(x, b), None
        out, _ = lax.scan(body, a, None, length=REPS)
        return out

    def chain_iszero(a, b):
        def body(x, _):
            z = L.fe_is_zero(x)
            return L.fe_add(x, b), z
        out, zs = lax.scan(body, a, None, length=REPS)
        return out, zs

    def chain_dbl(a, b):
        one = jnp.broadcast_to(jnp.asarray(L.int_to_limbs(1)).reshape(20, 1), a.shape)
        def body(P, _):
            return C.jacobian_double(*P), None
        out, _ = lax.scan(body, (a, b, one), None, length=REPS)
        return out

    def chain_addc(a, b):
        one = jnp.broadcast_to(jnp.asarray(L.int_to_limbs(1)).reshape(20, 1), a.shape)
        inf2 = jnp.zeros((B,), bool)
        def body(P, _):
            return C.jacobian_add_complete(*P, b, a, one, inf2), None
        out, _ = lax.scan(body, (a, b, one), None, length=REPS)
        return out

    t_mul = bench("fe_mul", chain_mul, a, b)
    t_sqr = bench("fe_sqr", chain_sqr, a)
    t_add = bench("fe_add", chain_add, a, b)
    t_conv = bench("conv only (no settle)", chain_conv_only, a, b)
    t_zero = bench("fe_is_zero (+add)", chain_iszero, a, b)
    bench("jacobian_double", chain_dbl, a, b)
    bench("jacobian_add_complete", chain_addc, a, b)

    # Full kernel for reference.
    def dsm(a, b):
        return C.double_scalar_mult(a, b, a % 1 + jnp.asarray(
            L.int_to_limbs(C.G_X)).reshape(20, 1) * jnp.ones((1, B), jnp.int32),
            jnp.asarray(L.int_to_limbs(C.G_Y)).reshape(20, 1) * jnp.ones((1, B), jnp.int32))
    f = jax.jit(lambda a, b: C.jacobian_to_affine(*dsm(a, b)))
    _force(f(a, b))
    t0 = time.perf_counter(); _force(f(a, b))
    dt = time.perf_counter() - t0
    print(f"{'full dsm+affine':28s} {dt*1e3:8.1f} ms total  {dt/B*1e9:8.1f} ns/lane")
    print(f"settle share of fe_mul: {(t_mul - t_conv) / t_mul:.0%}")


if __name__ == "__main__":
    main()

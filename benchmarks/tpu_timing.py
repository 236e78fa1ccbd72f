"""Shared timing harness for the attention benchmarks.

Three habits, kept in one place so long_context_tpu.py and the flash
tile sweeps measure the same way: loop `inner` steps inside ONE jitted
call so per-call dispatch cost is amortized over real kernel time,
synchronize by fetching a scalar reduced from the output (a host fetch
cannot return before the device finishes), and report the best of N
repetitions over distinct resident inputs.
"""

import time

import jax
import jax.numpy as jnp


def make_fwd_bwd_step(attn, prec, inner):
    """Jitted `inner`-step fwd+bwd loop over `attn(q, k, v, causal=True)`.

    `prec` is applied as the default matmul precision around the
    attention call (covers the dense path; the flash kernels take their
    precision as a kwarg, already bound into `attn` by the caller). Each
    iteration perturbs q so no dispatch repeats the previous one's
    inputs, and every gradient is fully reduced into the scalar result
    so none is dead code.
    """

    def step(q, k, v):
        def loss(q, k, v):
            with jax.default_matmul_precision(prec):
                out = attn(q, k, v, causal=True)
            return jnp.sum(out**2)

        def body(i, acc):
            qi = q * (1.0 + i.astype(jnp.float32) * 1e-6)
            l, gs = jax.value_and_grad(loss, argnums=(0, 1, 2))(qi, k, v)
            return acc + l + sum(jnp.sum(g) for g in gs)

        return jax.lax.fori_loop(0, inner, body, jnp.float32(0))

    return jax.jit(step)


def dispatch_floor() -> float:
    """Min wall time of a trivial jitted call + scalar fetch.

    Any per-call timing INCLUDES one such floor. Callers size `inner` so
    the floor is a small share of a call and subtract this estimate
    from the wall time.
    """
    f = jax.jit(lambda x: jnp.sum(x * x))
    x = jnp.ones((128, 128), jnp.float32)
    float(f(x))
    best = float("inf")
    for _ in range(6):
        t0 = time.perf_counter()
        float(f(x))
        best = min(best, time.perf_counter() - t0)
    return best


def timed(step, qs, ks, vs, reps, inner, floor_s: float | None = None):
    """Best-of-`reps` PER-STEP time over distinct resident inputs.

    Input set 0 is burned on compile+warmup; sets 1..reps are each timed
    individually (scalar fetch = completion barrier) and the MINIMUM is
    reported. The dispatch floor (see `dispatch_floor`) is subtracted
    from each call's wall time before the per-step division — measured
    here by default so EVERY caller of this harness is on the v2
    protocol; pass `floor_s` to reuse one measurement across many
    `timed` calls. Callers must still size `inner` so the floor is a
    small fraction of a call (the subtraction corrects the mean, not
    the noise).
    """
    if floor_s is None:
        floor_s = dispatch_floor()
    float(step(qs[0], ks[0], vs[0]))
    best = float("inf")
    for i in range(1, reps + 1):
        t0 = time.perf_counter()
        float(step(qs[i], ks[i], vs[i]))  # forces the call; fetches 4 bytes
        best = min(best, time.perf_counter() - t0)
    return max(best - floor_s, 0.0) / inner

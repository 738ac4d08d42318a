"""On-chip timing of the fused attention kernels and the stock pallas
flash kernel, with an in-jit scan loop so per-call dispatch cost
amortizes away.

CAVEAT (r5): the per-rep numbers include the carry reduction over the
(B, H, T, D) output (~6M-element fp32 sum per rep), which dominates the
kernels themselves at these shapes — treat the output as RELATIVE between
configurations sharing a loop shape, and use a device trace's table by
scope (scripts/trace_scope_table.py) for absolute per-kernel times. The r5 sweep's
relative result: 512/512 blocks remain best for fwd+bwd with dropout;
bq=1024/bk=512 ties within noise.

  python scripts/bench_attn_kernels.py [--sweep]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

B, T, H, D = 8, 1024, 12, 64
R = 30


def timed(make_fn, *args):
    f = jax.jit(make_fn)
    out = f(*args)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0].astype(jnp.float32)))
    t0 = time.perf_counter()
    out = f(*args)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0].astype(jnp.float32)))
    dt = time.perf_counter() - t0
    return dt / R * 1e3  # ms per rep


def main(sweep=False):
    from building_llm_from_scratch_tpu.ops import fused_attention as fa

    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (B, H, T, D), jnp.bfloat16)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, H, T, D),
                           jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, H, T, D),
                          jnp.bfloat16)
    do = jax.random.normal(jax.random.fold_in(k, 3), (B, H, T, D),
                           jnp.bfloat16)
    seed = jnp.zeros((1, 2), jnp.int32)
    scale = 1.0 / D ** 0.5

    combos = [(512, 512)]
    if sweep:
        combos = [(512, 512), (1024, 512), (512, 1024), (1024, 1024),
                  (256, 512), (512, 256), (256, 1024), (1024, 256)]

    for rate in (0.0, 0.1):
        for bq, bk in combos:
            def fwd_loop(q, kk, v):
                def body(c, _):
                    o, l = fa._fwd(q, kk, v, seed, scale=scale, rate=rate,
                                   bq=bq, bk=bk)
                    return c + jnp.sum(o.astype(jnp.float32)), None
                c, _ = jax.lax.scan(body, jnp.zeros(()), None, length=R)
                return c

            def bwd_loop(q, kk, v, do):
                o, lse = fa._fwd(q, kk, v, seed, scale=scale, rate=rate,
                                 bq=bq, bk=bk)

                def body(c, _):
                    dq, dk, dv = fa._bwd(q, kk, v, seed, o, lse, do,
                                         scale=scale, rate=rate, bq=bq,
                                         bk=bk)
                    return c + jnp.sum(dq.astype(jnp.float32)), None
                c, _ = jax.lax.scan(body, jnp.zeros(()), None, length=R)
                return c

            t_f = timed(fwd_loop, q, kk, v)
            t_b = timed(bwd_loop, q, kk, v, do)
            print(f"rate={rate} bq={bq:4d} bk={bk:4d}: "
                  f"fwd {t_f:6.3f} ms  bwd(dq+dkv) {t_b:6.3f} ms  "
                  f"total {t_f + t_b:6.3f}", flush=True)

    # stock pallas flash (no dropout) for reference
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    bs = BlockSizes(block_q=512, block_k_major=512, block_k=512, block_b=1,
                    block_q_major_dkv=512, block_k_major_dkv=512,
                    block_k_dkv=512, block_q_dkv=512,
                    block_k_major_dq=512, block_k_dq=512, block_q_dq=512)

    def stock_loop(q, kk, v, do):
        def f(q, kk, v):
            return jnp.sum(flash_attention(
                q, kk, v, causal=True, sm_scale=scale,
                block_sizes=bs).astype(jnp.float32) * do.astype(jnp.float32))

        def body(c, _):
            l, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(q, kk, v)
            return c + jnp.sum(grads[0].astype(jnp.float32)), None
        c, _ = jax.lax.scan(body, jnp.zeros(()), None, length=R)
        return c

    t_s = timed(stock_loop, q, kk, v, do)
    print(f"stock flash fwd+bwd (no dropout): {t_s:6.3f} ms", flush=True)


if __name__ == "__main__":
    main(sweep="--sweep" in sys.argv)

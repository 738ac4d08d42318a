"""Breakdown timing for the headline config (GPT2-124M bf16 bs4 ctx1024).

Times (each ending in a blocking fetch of the result): fwd-only, fwd+bwd,
full step; each with dropout on/off; plus attention micro-bench per impl
at the headline shape with/without dropout. Run on the real chip:

  python scripts/profile_headline.py
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from building_llm_from_scratch_tpu.configs import get_config
from building_llm_from_scratch_tpu.models import init_params
from building_llm_from_scratch_tpu.models.transformer import forward
from building_llm_from_scratch_tpu.training import (
    build_optimizer, get_policy, init_train_state, make_train_step,
)
from building_llm_from_scratch_tpu.training.train_step import (
    cross_entropy_loss, make_full_params_fn,
)
from building_llm_from_scratch_tpu.utils.seeding import configure_default_prng

configure_default_prng()

B, T = 4, 1024
ITERS = 20


def sync(x):
    return float(jnp.sum(jax.tree_util.tree_leaves(x)[0].astype(jnp.float32)))


def timeit(fn, *args):
    out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / ITERS * 1e3  # ms


def bench_model(drop):
    cfg = get_config("GPT2", "124M", dtype="fp32")
    if not drop:
        cfg = cfg.replace(drop_rate=0.0)
    policy = get_policy("bf16")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "inputs": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
        "weights": jnp.ones((B, T), jnp.float32),
    }
    full = make_full_params_fn(cfg, policy=policy)
    key = jax.random.PRNGKey(1)

    @jax.jit
    def fwd(p):
        pp = full(p, {})
        logits = forward(pp, cfg, batch["inputs"], rng=key,
                        deterministic=(cfg.drop_rate <= 0.0))
        return cross_entropy_loss(logits, batch["targets"], batch["weights"])

    grad = jax.jit(jax.value_and_grad(fwd))

    opt = build_optimizer(total_steps=ITERS + 5)
    state = init_train_state(params, opt, jax.random.PRNGKey(0), policy=policy)
    step = make_train_step(cfg, opt, policy=policy)

    t_fwd = timeit(fwd, params)
    t_grad = timeit(lambda p: grad(p)[0], params)

    def run_step(s, b):
        s2, m = step(s, b)
        return m["loss"], s2
    # step donates; keep threading state
    out = step(state, batch); sync(out[1]["loss"]); state = out[0]
    t0 = time.perf_counter()
    for _ in range(ITERS):
        state, m = step(state, batch)
    sync(m["loss"])
    t_step = (time.perf_counter() - t0) / ITERS * 1e3

    tag = "drop0.1" if drop else "drop0.0"
    tok = B * T
    print(f"[{tag}] fwd {t_fwd:7.2f} ms | fwd+bwd {t_grad:7.2f} ms | "
          f"step {t_step:7.2f} ms | {tok / t_step * 1e3:8.0f} tok/s")


def bench_attn():
    from building_llm_from_scratch_tpu.ops.attention import causal_attention
    H, D = 12, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, D), jnp.bfloat16)
    rng = jax.random.PRNGKey(3)

    for impl in ("xla", "flash", "pallas", "fused"):
        for drop in (0.0, 0.1):
            if impl == "pallas" and drop > 0:
                continue

            def f(q, k, v):
                def g(q, k, v):
                    o = causal_attention(q, k, v, dropout_rate=drop,
                                         dropout_rng=rng,
                                         deterministic=(drop == 0.0), impl=impl)
                    return jnp.sum(o.astype(jnp.float32) ** 2)
                return jax.grad(g, argnums=(0, 1, 2))(q, k, v)

            jf = jax.jit(f)
            try:
                t = timeit(jf, q, k, v)
                print(f"attn {impl:7s} drop={drop}: {t:6.2f} ms (fwd+bwd)")
            except Exception as e:
                print(f"attn {impl:7s} drop={drop}: FAILED {type(e).__name__}: {e}")


if __name__ == "__main__":
    print(f"backend={jax.default_backend()} devices={jax.devices()}")
    bench_model(drop=True)
    bench_model(drop=False)
    bench_attn()

"""The serving cells' five compiled programs as a contract: dump their
optimised HLO for a DESCRIBED v5e (no chip needed, nothing runs), and
compare two dumps with everything that only names a source stripped.

    python scripts/serving_hlo.py dump <tree> <outdir> [program ...]
    python scripts/serving_hlo.py cmp <outdir_a> <outdir_b>
    python scripts/serving_hlo.py lower <tree> [program ...]

``dump`` builds the programs as ``DecodeEngine`` jits them (cache donated)
from the benchmark's own configuration and traffic files, at the cells'
sizes: ``gpt2-1.5b``, 32 slots: ``_decode_impl`` and ``_prefill_impl`` at
the smallest and largest bucket the chat cell warms; the
``command-a-plus-05-2026`` cut and the ``solar-open2-250b`` cut, 48 slots
each, and ``ai21-jamba2-3b`` whole, 192 slots: ``_decode_impl`` and
``_chunk_impl`` at 512 (nine programs; the first five are the contract of
PR 30, two exist from PR 35 on and the last two from PR 39 on). ``<tree>`` is a checkout (``git archive <commit> |
tar -x -C <dir>`` for a parent), so two commits are dumped by two calls.
Run with ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` beside a test run. About 20 s a
program.

``cmp`` strips ``metadata={...}``, the header's source tables and the
location attributes inside each Mosaic kernel's serialised body (parsed and
printed again without debug info), then says IDENTICAL, or SAME UP TO
INSTRUCTION NAMES (numeric suffixes renumbered by first appearance: the
same operations on the same operands in the same scheduled order), or
writes the differing lines beside the dumps; and, from the metadata it
strips, whether as many operations sit under each ``jax.named_scope`` the
by-scope trace table reads (``scripts/trace_scope_table.py``). A refactor of
``models/transformer.py``'s slot pass or of the engine's builders holds the
first; the order in which a program's jaxpr creates its operations shows in
the second (PERF.md section 6, PR 30).

``lower`` builds the same programs and stops before the compiler: the
seconds of ``jit(...).lower()`` alone, which is the tracing and the kernels'
lowering to Mosaic in Python that every process repeats before it can ask
the compile cache (a warm ``setup_s``: PERF.md section 6, PRs 34 and 43).
Run it where the number is wanted (the chip's host is slower at it than
this sandbox), one tree a process, the programs in the order a cell's
process builds them (the chunk before the tick).
"""

import base64
import difflib
import hashlib
import json
import os
import re
import sys
import time


def dump(tree: str, outdir, only: set) -> None:
    """``outdir`` None: lower only, and print the seconds."""
    sys.path.insert(0, tree)
    if outdir is not None:
        os.makedirs(outdir, exist_ok=True)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(desc.devices[0])
    jax.default_backend = lambda: "tpu"     # the programs' trace-time rules

    from building_llm_from_scratch_tpu.configs import ModelConfig
    from building_llm_from_scratch_tpu.models import transformer as tf
    from building_llm_from_scratch_tpu.serving import engine as eng
    from building_llm_from_scratch_tpu.serving.kvcache import KVCachePolicy

    assert eng.__file__.startswith(os.path.abspath(tree)), eng.__file__
    I32, F32, U32 = jnp.int32, jnp.float32, jnp.uint32
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    shapes = lambda f, *a: jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(f, *a))

    def cell(config_file, traffic_file):
        """An engine that holds nothing (no weights, no cache): only what
        its program builders read of it."""
        load = lambda *p: json.load(open(os.path.join(tree, "benchmark", *p)))
        config, traffic = (load("configs", config_file),
                           load("traffic", traffic_file))
        cfg = ModelConfig(**config["model"])
        opts = traffic["engine"]
        e = object.__new__(eng.DecodeEngine)
        e.cfg, e.n_slots, e.max_top_k = cfg, opts["n_slots"], opts["max_top_k"]
        e.spec_k, e._paged = 0, False
        e._cache_shardings = e._sp_sharding = e.mesh_plan = None
        e.kv_policy = KVCachePolicy(**opts.get("kv_policy", {}))
        e.max_len = e._cache_len = cfg.context_length
        e.warmup_prompt_cap = traffic["prompt"]["max"]
        params = shapes(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
        blocks = (None if cfg.is_moe
                  else shapes(lambda p: tf.unstack_blocks(p, cfg), params))
        cache = shapes(lambda: tf.init_slot_cache(
            cfg, e.n_slots, e._cache_len, policy=e.kv_policy))
        return e, cache, (params, blocks)

    def one_program(name, fn, *args):
        if only and name not in only:
            return
        t0 = time.time()
        lowered = jax.jit(fn, donate_argnums=(0,)).lower(*args)
        if outdir is None:
            print(name, f"lowered in {time.time() - t0:.3f}s", flush=True)
            return
        compiled = lowered.compile()
        with open(os.path.join(outdir, name + ".hlo"), "w") as f:
            f.write(compiled.as_text())
        mem = compiled.memory_analysis()
        print(name, f"{time.time() - t0:.0f}s; temporaries",
              mem.temp_size_in_bytes, "arguments",
              mem.argument_size_in_bytes, flush=True)

    key, scalar = sds((2,), U32), lambda dt: sds((), dt)
    e, cache, weights = cell("gpt2-1.5b.json", "chat_poisson_lognormal.json")
    S = e.n_slots
    row = lambda dt: sds((S,), dt)
    buckets = e.prompt_buckets()
    for Tpb in (buckets[0], buckets[-1]):
        one_program(f"gpt2_prefill_{Tpb}", e._prefill_impl, cache, weights,
                    sds((1, Tpb), I32), scalar(I32), scalar(I32), key,
                    scalar(F32), scalar(I32))
    one_program("gpt2_decode", e._decode_impl, cache, weights, row(I32),
                row(I32), sds((S, 2), U32), row(I32), row(F32), row(I32))

    e, cache, weights = cell("command-a-plus-05-2026.json",
                             "rag_mixed_poisson.json")
    S, C = e.n_slots, e.kv_policy.prefill_chunk
    one_program(f"rag_chunk_{C}", e._chunk_impl, cache, weights,
                sds((1, C), I32), scalar(I32), scalar(I32), scalar(I32), key,
                scalar(F32), scalar(I32))
    one_program("rag_decode", e._decode_impl, cache, weights, row(I32),
                row(I32), sds((S, 2), U32), row(I32), row(F32), row(I32),
                None, None, None, sds((S,), jnp.bool_))

    if not os.path.exists(os.path.join(tree, "benchmark", "configs",
                                       "solar-open2-250b.json")):
        return                      # a tree from before PR 35
    e, cache, weights = cell("solar-open2-250b.json",
                             "longdoc_mixed_poisson.json")
    S, C = e.n_slots, e.kv_policy.prefill_chunk
    one_program(f"longdoc_chunk_{C}", e._chunk_impl, cache, weights,
                sds((1, C), I32), scalar(I32), scalar(I32), scalar(I32), key,
                scalar(F32), scalar(I32))
    one_program("longdoc_decode", e._decode_impl, cache, weights, row(I32),
                row(I32), sds((S, 2), U32), row(I32), row(F32), row(I32),
                None, None, None, sds((S,), jnp.bool_))

    if not os.path.exists(os.path.join(tree, "benchmark", "configs",
                                       "ai21-jamba2-3b.json")):
        return                      # a tree from before PR 39
    e, cache, weights = cell("ai21-jamba2-3b.json", "chat_wide_poisson.json")
    S, C = e.n_slots, e.kv_policy.prefill_chunk
    one_program(f"widechat_chunk_{C}", e._chunk_impl, cache, weights,
                sds((1, C), I32), scalar(I32), scalar(I32), scalar(I32), key,
                scalar(F32), scalar(I32))
    one_program("widechat_decode", e._decode_impl, cache, weights, row(I32),
                row(I32), sds((S, 2), U32), row(I32), row(F32), row(I32),
                None, None, None, sds((S,), jnp.bool_))


SCOPES = ("attention", "window_attention", "linear_attention",
          "linear_conv", "linear_gates", "linear_proj", "attention_gate",
          "selective_scan", "ssm_conv", "ssm_proj", "cache_update", "mlp",
          "moe_router", "moe_experts", "moe_shared", "head", "sampling")


def compare(a: str, b: str) -> int:
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    seen = {}

    def body(m):
        raw = m.group(1)
        if raw not in seen:
            with ctx:
                mod = ir.Module.parse(base64.b64decode(raw))
                txt = mod.operation.get_asm(enable_debug_info=False)
            seen[raw] = hashlib.sha256(txt.encode()).hexdigest()
        return '"body":"sha256:%s"' % seen[raw]

    def clean(t):
        head, rest = t.split("\n", 1)
        if "StackFrames\n" in rest:         # the last of the source tables
            rest = rest[rest.index("StackFrames\n"):]
            rest = rest[rest.index("\n\n"):]
        t = re.sub(r",? ?metadata=\{[^}]*\}", "", head + rest)
        return re.sub(r'"body":"([A-Za-z0-9+/=]+)"', body, t)

    def renumber(t):
        names, count = {}, {}

        def one(m):
            name = m.group(0)
            if name not in names:
                stem = re.sub(r"\.\d+", "", name[1:])
                count[stem] = count.get(stem, 0) + 1
                names[name] = f"%{stem}#{count[stem]}"
            return names[name]

        return re.sub(r"%[\w.\-<>]+", one, t)

    def scopes(t):
        names = [s for m in re.finditer(r'op_name="([^"]*)"', t)
                 for s in m.group(1).split("/") if s in SCOPES]
        return {s: names.count(s) for s in sorted(set(names))}

    worst = 0
    for n in sorted(os.listdir(a)):
        if not n.endswith(".hlo"):
            continue
        raw = [open(os.path.join(d, n)).read() for d in (a, b)]
        if scopes(raw[0]) != scopes(raw[1]):
            worst = max(worst, 1)
            print(n, "SCOPES MOVED:", scopes(raw[0]), "->", scopes(raw[1]))
        ta, tb = map(clean, raw)
        la, lb = ta.split("\n"), tb.split("\n")
        if ta == tb:
            print(n, "IDENTICAL:", len(la), "lines")
        elif renumber(ta) == renumber(tb):
            worst = max(worst, 1)
            print(n, "SAME UP TO INSTRUCTION NAMES:", len(la), "lines,",
                  sum(x != y for x, y in zip(la, lb)),
                  "of them name another suffix")
        else:
            worst = 2
            d = [x[:600] for x in difflib.unified_diff(la, lb, lineterm="",
                                                       n=0)
                 if not x.startswith(("---", "+++", "@@"))]
            out = os.path.join(b, n + ".diff")
            open(out, "w").write("\n".join(d))
            print(n, "DIFFERS:", len(d), "lines ->", out)
    return worst


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "dump":
        dump(os.path.abspath(sys.argv[2]), sys.argv[3], set(sys.argv[4:]))
    elif len(sys.argv) >= 3 and sys.argv[1] == "lower":
        dump(os.path.abspath(sys.argv[2]), None, set(sys.argv[3:]))
    elif len(sys.argv) == 4 and sys.argv[1] == "cmp":
        sys.exit(compare(sys.argv[2], sys.argv[3]))
    else:
        sys.exit(__doc__)
